import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable (e)).

Lowers + compiles every (architecture x input shape) on the production
single-pod (16,16) mesh and the 2-pod (2,16,16) mesh -- ShapeDtypeStructs
only, nothing allocated -- then records memory analysis, cost analysis, and
the parsed collective schedule for the roofline table (EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

The two os.environ lines above MUST stay the first executable lines: jax
locks the device count on first init, and only the dry-run wants 512 host
devices.  (No `from __future__` here for that same reason -- py>=3.10 types
only.)
"""
import argparse
import json
import time
import traceback

import jax

from repro.analysis import hlo_stats as HS
from repro.analysis import roofline as RL
from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, get_arch
from repro.core.flatparam import MeshTopo, count_params
from repro.core.loco import SyncConfig
from repro.core.quantizer import QuantConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (RunConfig, build_model, make_decode_step,
                                make_prefill_step, make_train_step)

SKIPS: dict[tuple[str, str], str] = {
    # long_500k needs sub-quadratic attention (DESIGN.md §6)
    ("chameleon-34b", "long_500k"): "full attention; 500k KV cache infeasible",
    ("qwen3-moe-30b-a3b", "long_500k"): "full attention; 500k KV cache infeasible",
    ("minicpm-2b", "long_500k"): "full attention; 500k KV cache infeasible",
    ("gemma2-27b", "long_500k"): "global layers are full attention at 500k",
    ("command-r-35b", "long_500k"): "full attention; 500k KV cache infeasible",
    ("whisper-small", "long_500k"): "enc-dec ASR; 500k-token decode not meaningful",
}


def default_run(cfg: ArchConfig, sync_strategy: str = "loco") -> RunConfig:
    return RunConfig(
        sync=SyncConfig(strategy=sync_strategy, quant=QuantConfig(mode="block")),
        optimizer="adam",
        microbatch=1,
        remat=True,
    )


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               sync_strategy: str = "loco", out_dir: str | None = None,
               run_overrides: dict | None = None) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    key = (arch, shape_name)
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "sync": sync_strategy}
    if key in SKIPS:
        rec.update(status="skipped", reason=SKIPS[key])
        return _emit(rec, out_dir)

    mesh = make_production_mesh(multi_pod=multi_pod)
    topo = MeshTopo.from_mesh(mesh)
    t0 = time.time()
    try:
        if shape.kind == "train":
            run = default_run(cfg, sync_strategy)
            if run_overrides:
                import dataclasses as _dc
                run = _dc.replace(run, **run_overrides)
            bundle = make_train_step(cfg, run, mesh, shape)
        elif shape.kind == "prefill":
            bundle = make_prefill_step(cfg, mesh, shape)
        else:
            bundle = make_decode_step(cfg, mesh, shape)

        lowered = bundle.fn.lower(*bundle.input_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):  # newer jax: one dict per program
            ca = ca[0] if ca else {}
        hlo = compiled.as_text()
        # trip-count-aware static analysis (cost_analysis counts scan bodies
        # once -- see analysis/hlo_stats.py)
        st = HS.analyze(hlo)
        flops = st.flops
        hbm_bytes = st.bytes
        terms = RL.roofline_terms(flops, hbm_bytes, st.wire_bytes)

        model = build_model(cfg, topo.tp)
        n_params = count_params(model.groups())
        if cfg.n_experts and cfg.top_k:
            active_frac_ffn = cfg.top_k / cfg.n_experts
            # crude split: expert params vs the rest
            expert_params = cfg.n_layers * cfg.n_experts * cfg.d_ff * cfg.d_model * (
                3 if cfg.mlp in ("swiglu", "geglu") else 2)
            n_active = n_params - expert_params + expert_params * active_frac_ffn
        else:
            n_active = n_params
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            model_flops_global = RL.model_flops_per_step(n_active, tokens)
        elif shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            model_flops_global = 2.0 * n_active * tokens
        else:
            tokens = shape.global_batch  # one token per sequence
            model_flops_global = 2.0 * n_active * tokens
        n_dev = mesh.devices.size
        model_flops_dev = model_flops_global / n_dev

        fid_rec = None
        if shape.kind == "train" and bundle.probe_fn is not None:
            # predicted probe-step overhead (DESIGN.md §17): compile the
            # probe variant and diff its collective schedule against the
            # primary module — the extra wire bytes are the reference
            # reduces, the extra launches include the probe's flat
            # (non-overlapped) schedule when the primary is pipelined
            probe_hlo = (bundle.probe_fn.lower(*bundle.input_shapes)
                         .compile().as_text())
            pst = HS.analyze(probe_hlo)
            all_kinds = set(pst.coll_counts) | set(st.coll_counts)
            delta = {k: round(pst.coll_counts.get(k, 0.0)
                              - st.coll_counts.get(k, 0.0))
                     for k in sorted(all_kinds)}
            fid_rec = dict(
                every=run.fidelity_every,
                probe_wire_bytes=round(pst.wire_bytes),
                extra_wire_bytes=round(pst.wire_bytes - st.wire_bytes),
                probe_launches={k: round(v)
                                for k, v in pst.coll_counts.items()},
                extra_launches={k: v for k, v in delta.items() if v},
            )
        moe_rec = None
        if shape.kind == "train":
            # ep_a2a dispatch/combine traffic on the TP axis (DESIGN.md §18)
            from repro.telemetry import wire as WIRE
            moe_rec = WIRE.moe_a2a_report(cfg, shape, topo, run.microbatch)
        wire_tiers = None
        if shape.kind == "train" and bundle.helpers.get("plan") is not None:
            # per-tier cadence + capacity-vs-effective bytes (DESIGN.md §16)
            from repro.telemetry import wire as WIRE
            _topo = bundle.helpers["topo"]
            _rep = WIRE.plan_report(bundle.helpers["plan"],
                                    pods=_topo.pods, wans=_topo.wans)
            wire_tiers = [t.record() for t in _rep.tiers]

        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            n_params=n_params,
            n_params_active=n_active,
            memory=dict(
                argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes,
                peak_bytes=ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
            ),
            flops_per_device=flops,
            hbm_bytes_per_device=hbm_bytes,
            xla_cost_analysis=dict(flops=float(ca.get("flops", 0.0)),
                                   bytes=float(ca.get("bytes accessed", 0.0))),
            collectives=dict(counts={k: round(v) for k, v in st.coll_counts.items()},
                             bytes_by_kind={k: round(v) for k, v in st.coll_bytes.items()},
                             wire_bytes=round(st.wire_bytes)),
            wire_tiers=wire_tiers,
            moe_a2a=moe_rec,
            fidelity=fid_rec,
            roofline=terms,
            model_flops_per_device=model_flops_dev,
            useful_flops_ratio=(model_flops_dev / flops) if flops else None,
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-3000:])
    return _emit(rec, out_dir)


def _emit(rec: dict, out_dir: str | None) -> dict:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['sync']}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = ""
    if status == "ok":
        r = rec["roofline"]
        extra = (f" compile={rec['compile_s']}s peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                 f"dom={r['dominant']} c/m/n={r['compute_s']:.4f}/{r['memory_s']:.4f}/"
                 f"{r['collective_s']:.4f}s")
        if rec.get("wire_tiers"):
            # effective/capacity MiB per tier at its cadence (DESIGN.md §16)
            extra += " tiers=" + ",".join(
                f"{t['network']}@e{t['every']}:"
                f"{t['effective_bytes'] / 2**20:.2f}"
                f"/{t['capacity_bytes'] / 2**20:.2f}MiB"
                for t in rec["wire_tiers"])
        if rec.get("moe_a2a"):
            # compressed ep_a2a activation traffic per step (DESIGN.md §18)
            m = rec["moe_a2a"]
            extra += (f" moe_a2a={m['per_step_bytes'] / 2**20:.2f}MiB"
                      f"@{m['codec']}")
        if rec.get("fidelity"):
            # probe cadence + predicted probe-step overhead (DESIGN.md §17)
            f = rec["fidelity"]
            extra += (f" fid@e{f['every']}:"
                      f"+{f['extra_wire_bytes'] / 2**20:.2f}MiB"
                      f"/+{sum(f['extra_launches'].values())}launch")
    elif status == "skipped":
        extra = " " + rec["reason"]
    else:
        extra = " " + rec["error"][:160]
    print(f"[dryrun] {rec['arch']:20s} {rec['shape']:12s} {rec['mesh']:8s} {status}{extra}",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--sync", default="loco")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="enable the bucketed scheduler for train shapes "
                         "with this fp32 bucket target (MiB)")
    ap.add_argument("--policy", default=None,
                    help="per-bucket wire policy for train shapes, e.g. "
                         "'body=loco4+topk1%%+every4' (same grammar as "
                         "launch/train.py --policy); tier cadence and "
                         "capacity-vs-effective bytes land in the "
                         "wire_tiers record and the tiers= column")
    ap.add_argument("--fidelity-every", type=int, default=None,
                    help="also compile the fidelity-probe step variant for "
                         "train shapes and report the probe cadence plus "
                         "predicted probe-step overhead (extra wire bytes "
                         "and collective launches vs a normal step) in the "
                         "fid= column (DESIGN.md §17)")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="compile the primary train module on the legacy "
                         "flat schedule")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    overrides: dict = {}
    if args.bucket_mb is not None:
        overrides["bucket_bytes"] = int(args.bucket_mb * 2**20)
    if not args.overlap:
        overrides["overlap"] = False
    if args.fidelity_every is not None:
        overrides["fidelity_every"] = args.fidelity_every
    if args.policy:
        from repro.core import policy as POL
        # same base sync default_run builds, so presets inherit correctly
        overrides["policy"] = POL.parse_policy(
            args.policy,
            SyncConfig(strategy=args.sync, quant=QuantConfig(mode="block")))

    from repro.configs.all_archs import ASSIGNED

    combos = []
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))
    for a, s, mp in combos:
        if args.skip_existing:
            name = f"{a}__{s}__{'2x16x16' if mp else '16x16'}__{args.sync}.json"
            if os.path.exists(os.path.join(args.out, name)):
                print(f"[dryrun] {a} {s} exists, skip")
                continue
        dryrun_one(a, s, multi_pod=mp, sync_strategy=args.sync,
                   out_dir=args.out, run_overrides=overrides or None)


if __name__ == "__main__":
    main()
