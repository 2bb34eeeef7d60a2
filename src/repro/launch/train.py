"""Training driver.

CPU-scale real training (reduced configs / llama2-400m) and the config
surface a cluster launch would use.  Examples:

  PYTHONPATH=src python -m repro.launch.train --arch llama2-400m --reduced \\
      --steps 200 --seq-len 128 --global-batch 8 --dp 2 --tp 2 \\
      --sync loco --log-every 10

  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --reduced \\
      --sync fp --optimizer adamw
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import re
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as CKPT
from repro.configs.base import ShapeConfig, get_arch, reduced
from repro.core import policy as POL
from repro.core.loco import SyncConfig
from repro.core.quantizer import QuantConfig
from repro.data.synthetic import DataConfig, make_batch_fn, make_whisper_batch_fn
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.launch.steps import (RunConfig, make_init, make_train_step,
                                state_fingerprint)
from repro.telemetry import profiler as PROF
from repro.telemetry import sink as SINK
from repro.telemetry import wire as WIRE


def build_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pods", type=int, default=0)
    ap.add_argument("--wans", type=int, default=0,
                    help="size of the outermost WAN mesh axis for 3-tier "
                         "sync schedules (policy flag "
                         "'...+wan:topkN%%everyK'); needs --pods >= 2")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--sync", default="loco",
                    choices=["fp", "loco", "ef", "naive4", "onebit", "topk"])
    ap.add_argument("--quant-mode", default="block",
                    choices=["block", "fixed", "tensor"])
    ap.add_argument("--quant-scale", type=float, default=2.0**17)
    ap.add_argument("--error-codec", default="f8", choices=["f8", "bf16", "none"])
    ap.add_argument("--beta", type=float, default=0.5)
    ap.add_argument("--reset-every", type=int, default=512)
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--moe-a2a", default=None,
                    choices=["fp", "block8", "block8+ef"],
                    help="codec for the ep_a2a MoE dispatch/combine "
                         "all_to_all (core/act_comm): fp = raw bf16 "
                         "(bit-exact legacy path), block8 = stateless int8 "
                         "block-absmax fwd+bwd, block8+ef = block8 plus a "
                         "persistent combine-side error-feedback state")
    ap.add_argument("--hierarchical", action="store_true",
                    help="two-stage (pod, data) exchange for every bucket: "
                         "the bucket's codec intra-pod, 8-bit block across "
                         "pods; needs --pods >= 2. Per-bucket control via "
                         "--policy '...+hier'")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="bucketed sync: target MiB of fp32 gradient per "
                         "bucket (0 = monolithic legacy path)")
    ap.add_argument("--policy", default="",
                    help="per-bucket wire policy, e.g. "
                         "'embed=loco8,norm=fp,min=65536' or "
                         "'body=loco4+kernels' to enable the Pallas fast "
                         "paths per tensor class "
                         "(see repro.core.policy.parse_policy)")
    ap.add_argument("--coalesce", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pack the bucketed sync's wire leaves by exchange "
                         "signature and launch one collective per comm "
                         "group per step (bit-exact; --no-coalesce keeps "
                         "the legacy one-collective-per-bucket-leaf "
                         "schedule)")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pipeline the coalesced bucketed sync: readiness-"
                         "ordered stages with encode(k+1) barrier-pinned "
                         "into exchange(k)'s async window over double-"
                         "buffered pack buffers (bit-exact; --no-overlap "
                         "keeps the single-sync-region schedule)")
    ap.add_argument("--telemetry", action="store_true",
                    help="compute the in-graph compression-health metrics "
                         "(error norms, saturation/clip rates, scale stats, "
                         "update ratios) inside the jitted step -- no extra "
                         "collectives (DESIGN.md §14)")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="stream structured telemetry records to a JSONL "
                         "file (header/step/warning/summary schema, "
                         "repro.telemetry.sink); implies --telemetry")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="step record cadence for --metrics-jsonl "
                         "(0 = follow --log-every)")
    ap.add_argument("--fidelity-every", type=int, default=0,
                    help="gradient-fidelity probe cadence (DESIGN.md §17): "
                         "every N-th step runs the separately-compiled "
                         "probe variant that also reduces the exact fp32 "
                         "mean gradient and emits per-unit cosine / "
                         "relative-L2 / compensation-gain metrics with "
                         "per-tier attribution (0 = never; non-probe "
                         "steps are bit- and launch-identical to "
                         "--fidelity-every 0)")
    ap.add_argument("--profile-steps", default=None, metavar="N[:M]",
                    help="capture a jax.profiler trace for the inclusive "
                         "step window N:M (phase annotation via "
                         "loco/encode|exchange|decode|apply scopes)")
    ap.add_argument("--profile-dir",
                    default=os.path.join(tempfile.gettempdir(), "loco_trace"),
                    help="output dir for --profile-steps traces")
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine")
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="prune checkpoint history to the newest N "
                         "(0 = keep all)")
    ap.add_argument("--resume-reshard", action="store_true",
                    help="when resuming onto a different dp size / bucket "
                         "layout / policy / hierarchy setting, migrate the "
                         "checkpointed state (master chunks, optimizer "
                         "moments, per-bucket compensation errors) through "
                         "logical space instead of failing on the layout "
                         "mismatch")
    return ap.parse_args(argv)


def make_run(args) -> RunConfig:
    sync = SyncConfig(
        strategy=args.sync,
        quant=QuantConfig(mode=args.quant_mode, scale=args.quant_scale,
                          error_codec=args.error_codec),
        beta=args.beta,
        reset_every=args.reset_every,
        use_kernels=args.use_kernels,
        hierarchical=args.hierarchical,
    )
    policy = POL.parse_policy(args.policy, sync) if args.policy else None
    return RunConfig(sync=sync, optimizer=args.optimizer, lr=args.lr,
                     schedule=args.schedule, warmup_steps=args.warmup,
                     total_steps=args.steps, microbatch=args.microbatch,
                     bucket_bytes=int(args.bucket_mb * (1 << 20)),
                     policy=policy, coalesce=args.coalesce,
                     overlap=args.overlap,
                     telemetry=args.telemetry or bool(args.metrics_jsonl),
                     fidelity_every=args.fidelity_every)


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at one fixed place.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and the directory is left as it is; otherwise it goes to
    ``.jax_cache/`` at the checkout root.  The path is part of each entry's
    key, so it never depends on a pid, a time or a temporary directory.
    Call it before the first compile: JAX decides once per process whether
    the cache is used.

    The HLO's metadata (the ``loco/*`` and ``model/*`` scopes of
    ``telemetry/profiler``, source locations) is part of the key: a trace
    is read by those names, and an entry compiled from the same program
    with other scopes would carry the wrong ones.  Source files are named
    from the checkout root, so a checkout elsewhere finds the same entries.
    """
    root = Path(__file__).resolve().parents[3]
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{root}{os.sep}"))
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class TrainResult:
    """What one :func:`main` run observed, for callers that check it."""

    loss: float                  # last step's loss (nan when nothing ran)
    losses: dict[int, float]     # loss of every logged step
    compile_s: float | None      # ahead-of-time compile of the step program
    first_step_s: float | None   # first executed step (one-time warm-up)
    run_s: float                 # the steps after the first, end to end
    n_run: int                   # how many steps run_s covers
    custom_calls: int            # Pallas kernels (tpu_custom_call) in the step
    n_devices: int               # devices of the mesh
    state_bytes: dict[int, int]  # train-state bytes resident per device id
    peak_bytes: dict[int, int]   # peak_bytes_in_use per device id, where known


def _state_bytes(tree) -> dict[int, int]:
    out: dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def _peak_bytes(mesh) -> dict[int, int]:
    out = {}
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out[d.id] = int(stats["peak_bytes_in_use"])
    return out


def main(argv=None) -> TrainResult:
    args = build_args(argv)
    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.moe_a2a:
        if cfg.moe_impl != "ep_a2a" or not cfg.n_experts:
            raise SystemExit(f"--moe-a2a: {cfg.name} has no ep_a2a MoE "
                             "dispatch to compress")
        cfg = dataclasses.replace(cfg, moe_a2a_codec=args.moe_a2a)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=bool(args.pods > 1))
    else:
        mesh = make_local_mesh(dp=args.dp, tp=args.tp,
                               pods=args.pods if args.pods else None,
                               wans=args.wans if args.wans else None)
    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    run = make_run(args)

    init_fn, _ = make_init(cfg, run, mesh, shape)
    chunks, states, opt = init_fn(jax.random.PRNGKey(args.seed))
    bundle = make_train_step(cfg, run, mesh, shape)
    topo = bundle.helpers["topo"]
    plan = bundle.helpers["plan"]
    wire_rep = (WIRE.plan_report(plan, pods=topo.pods, wans=topo.wans)
                if plan is not None else None)
    if wire_rep is not None:
        print(WIRE.format_report(wire_rep), flush=True)
    moe_rep = WIRE.moe_a2a_report(cfg, shape, topo, run.microbatch)
    if moe_rep is not None:
        print(WIRE.format_moe_a2a(moe_rep), flush=True)
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                    global_batch=args.global_batch, seed=args.seed)
    batch_fn = (make_whisper_batch_fn(dc, cfg.d_model, cfg.dec_len)
                if cfg.enc_dec else make_batch_fn(dc))

    # the *target* plan's fingerprint is built before any restore, so a
    # layout change either reshards explicitly or fails loudly up front
    ckpt_fp = state_fingerprint(run, bundle.helpers["groups"], topo, plan,
                                arch=cfg, shape=shape)
    start = 0
    if args.ckpt_dir:
        latest = CKPT.latest_step(args.ckpt_dir)
        if latest is not None:
            state = CKPT.restore(args.ckpt_dir, latest,
                                 {"chunks": chunks, "states": states, "opt": opt},
                                 fingerprint=ckpt_fp,
                                 reshard=args.resume_reshard)
            chunks, states, opt = state["chunks"], state["states"], state["opt"]
            start = latest
            print(f"restored step {latest}")

    sink = None
    if args.metrics_jsonl:
        sink = SINK.MetricsSink(args.metrics_jsonl, header=dict(
            run={k: v for k, v in vars(args).items()},
            fingerprint=ckpt_fp,
            topo=dict(dp=topo.dp, tp=topo.tp, pods=topo.pods, wans=topo.wans,
                      dp_axes=list(topo.dp_axes), tp_axis=topo.tp_axis,
                      devices=int(mesh.devices.size)),
            **({"moe_a2a": moe_rep} if moe_rep is not None else {}),
        ))
        if wire_rep is not None:
            sink.write(wire_rep.record())
    metrics_every = args.metrics_every or args.log_every
    trace = (PROF.TraceSession(args.profile_dir,
                               PROF.parse_window(args.profile_steps))
             if args.profile_steps else None)

    def scalars(m):
        host = {k: float(v) for k, v in m.items()}
        return (host.pop("loss"), host.pop("gnorm"), host.pop("lr"), host)

    # compile the step ahead of time: the compile is set-up, not a step,
    # and the compiled program says how many Pallas kernels it launches
    compile_s, custom_calls, step_exe = None, 0, bundle.fn
    if start < args.steps:
        t_c = time.time()
        step_exe = bundle.fn.lower(chunks, states, opt, jnp.int32(start),
                                   batch_fn(jnp.int32(start))).compile()
        compile_s = time.time() - t_c
        custom_calls = step_exe.as_text().count(
            'custom_call_target="tpu_custom_call"')
        print(f"compiled step in {compile_s:.1f}s "
              f"({custom_calls} kernel custom calls)", flush=True)

    # the first executed step pays one-time warm-up; block on it separately
    # and start the run clock after it completes.
    peak_err = 0.0
    step_s: list[float] = []
    losses: dict[int, float] = {}
    first_s = None
    probe_compiled = False
    fid_every = run.fidelity_every
    t_run = t0 = time.time()
    m = None
    for step in range(start, args.steps):
        if trace is not None:
            trace.maybe_start(step)
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            t_step = time.time()
            batch = batch_fn(jnp.int32(step))
            # fidelity-probe dispatch (DESIGN.md §17): a host-side select of
            # the separately-compiled probe variant — the normal step stays
            # bit- and launch-identical to a probe-free run
            probe_step = (fid_every > 0
                          and step % fid_every == fid_every - 1)
            step_fn = bundle.probe_fn if probe_step else step_exe
            chunks, states, opt, m = step_fn(chunks, states, opt,
                                             jnp.int32(step), batch)
            log_step = step % args.log_every == 0 or step == args.steps - 1
            sink_step = sink is not None and (
                step % metrics_every == 0 or step == args.steps - 1)
            timed = sink is not None or trace is not None or first_s is None
            if timed:
                jax.block_until_ready(m["loss"])
                dt = time.time() - t_step
                if first_s is None:
                    first_s = dt
                    t_run = time.time()
                    print(f"first step {step} in {first_s:.1f}s", flush=True)
                elif probe_step and not probe_compiled:
                    probe_compiled = True  # first probe pays its own compile
                else:
                    step_s.append(dt)
        if trace is not None:
            trace.maybe_stop(step)
        if log_step or sink_step or (probe_step and sink is not None):
            loss, gnorm, lr, extra_m = scalars(m)
            losses[step] = loss
            fid_m = {k: extra_m.pop(k) for k in list(extra_m)
                     if k.startswith("fidelity/") or "/fid_" in k}
            peak_err = max(peak_err, extra_m.get("err_norm", 0.0))
            if sink is not None and probe_step and fid_m:
                sink.fidelity(step, metrics=fid_m)
            if sink_step:
                sink.step(step, loss=loss, gnorm=gnorm, lr=lr,
                          step_ms=step_s[-1] * 1e3 if step_s else None,
                          metrics=extra_m,
                          groups_inflight=bundle.helpers["groups_inflight"])
            if log_step:
                # post-warm-up throughput: the first executed step is
                # excluded from the clock
                n_run = step - start if first_s is not None else step - start + 1
                tok_s = (n_run * args.global_batch * args.seq_len
                         / max(time.time() - t_run, 1e-9))
                extra = (f" err_norm={extra_m['err_norm']:.3e}"
                         if "err_norm" in extra_m else "")
                if fid_m:
                    extra += (f" fid_cos={fid_m['fidelity/cos']:.4f}"
                              f" comp_gain={fid_m['fidelity/comp_gain']:.3f}")
                print(f"step {step:5d} loss={loss:.4f} "
                      f"gnorm={gnorm:.3f} lr={lr:.2e} "
                      f"tok/s={tok_s:,.0f}{extra}", flush=True)
        if args.ckpt_dir and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            CKPT.save(args.ckpt_dir, step + 1,
                      {"chunks": chunks, "states": states, "opt": opt},
                      fingerprint=ckpt_fp, keep=args.ckpt_keep)
    if trace is not None:
        trace.stop()
    if m is None:  # restored at/after the final step: nothing ran
        if sink is not None:
            sink.close()
        print("nothing to do (restored step >= --steps)")
        return TrainResult(float("nan"), {}, None, None, 0.0, 0, 0,
                           int(mesh.devices.size), {}, {})
    jax.block_until_ready(m["loss"])
    n_steps = args.steps - start
    n_run = max(n_steps - 1, 0)  # steps after the first
    run_dt = time.time() - t_run
    tok_s = n_run * args.global_batch * args.seq_len / max(run_dt, 1e-9)
    print(f"done: {n_steps} steps in {time.time()-t0:.1f}s "
          f"(compile {compile_s:.1f}s, first step {first_s:.1f}s, "
          f"run {run_dt:.1f}s, {tok_s:,.0f} tok/s post-compile)", flush=True)
    if sink is not None:
        sink.summary(
            steps=n_steps, compile_s=compile_s,
            step_ms=SINK.percentiles([s * 1e3 for s in step_s]),
            tokens_per_s=tok_s,
            wire_mib_per_step=(wire_rep.total_wire / 2**20
                               if wire_rep is not None else None),
            peak_err_norm=peak_err,
        )
        sink.close()
        print(f"telemetry: {sink.path}", flush=True)
    return TrainResult(
        loss=float(m["loss"]), losses=losses, compile_s=compile_s,
        first_step_s=first_s, run_s=run_dt, n_run=n_run,
        custom_calls=custom_calls, n_devices=int(mesh.devices.size),
        state_bytes=_state_bytes((chunks, states, opt)),
        peak_bytes=_peak_bytes(mesh))


if __name__ == "__main__":
    main()
