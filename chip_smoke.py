#!/usr/bin/env python3
"""Chip smoke test: the LoCo trainer end to end on a TPU.

Trains llama2-400m at full width and depth (24 layers, d_model 1024,
vocab 32000; random weights and synthetic data, both from seed 0) for six
steps through ``repro.launch.train.main``, the entry point a user calls,
and checks the losses against each other.

    python chip_smoke.py               # one chip: fp, loco, loco + kernels
    python chip_smoke.py --four-chips  # dp=4: fp, loco, loco --bucket-mb 4

Everything runs in this one process (a chip belongs to one process).  The
script fails, and prints no result, when JAX finds no TPU or when the
``repro`` package is not beside it.  Earlier lines report compile time,
post-compile step time, peak device memory and the Pallas kernel count of
each run; the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))

BASE = ["--arch", "llama2-400m", "--tp", "1", "--seq-len", "2048",
        "--global-batch", "8", "--steps", "6", "--log-every", "1"]

# Tolerances, in nats of the training loss (about 10.4 at step 0).
# Step 0 runs the same forward pass on the same weights and batch in every
# run; only the programs differ, which may fuse and reassociate the bf16
# reductions differently.  A wrong batch or weight would move a mean over
# 16k tokens by ~1e-2.
TOL_STEP0 = 1e-3
# LoCo's 4-bit compensated wire perturbs each of the six Adam updates; the
# paper's claim is loss parity with the fp wire, so after six steps the two
# may differ by a small part of what the loss fell.
BAND_LOCO = 0.05
# The fused kernels compute the jnp codec's math; they may differ from it
# by one f8 quantum of the stored error on rounding ties (see
# tests/test_kernels.py), which six steps cannot grow to more than this.
TOL_KERNELS = 2e-3
# The uniform-policy bucketed exchange moves the same bytes as the
# monolithic one (bit-exact on CPU); only the programs differ.
TOL_BUCKET = 2e-3


def fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    return 1


def train(name: str, argv: list[str]):
    from repro.launch import train as T

    print(f"--- {name}: {' '.join(argv)}", flush=True)
    res = T.main(argv)
    gc.collect()  # drop the last run's device buffers before the next
    step_s = res.run_s / max(res.n_run, 1)
    # printed whole as soon as the run ends, so that a later phase that
    # brings the process down leaves this one's numbers behind
    print(f"[{name}] compile_s={res.compile_s!r} "
          f"first_step_s={res.first_step_s!r} "
          f"step_s={step_s!r} (mean of {res.n_run} post-compile steps) "
          f"tpu_custom_calls={res.custom_calls} losses={res.losses} "
          f"state_bytes_per_device={res.state_bytes} "
          f"peak_bytes_in_use_per_device={res.peak_bytes} "
          "(process peak so far)", flush=True)
    return res


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


def check_losses(check: Checks, runs: dict, ref: str) -> None:
    for name, res in runs.items():
        vals = [res.losses[s] for s in sorted(res.losses)]
        check(f"{name}_finite", all(math.isfinite(v) for v in vals),
              f"losses {vals}")
        check(f"{name}_falls", vals[-1] < vals[0],
              f"step 0 {vals[0]!r} -> step {len(vals) - 1} {vals[-1]!r}")
    l0 = {n: r.losses[0] for n, r in runs.items()}
    spread = max(l0.values()) - min(l0.values())
    check("step0_equal", spread <= TOL_STEP0,
          f"spread {spread!r} <= {TOL_STEP0}; {l0}")
    for name, res in runs.items():
        if name != ref and name.startswith("loco"):
            d = abs(res.loss - runs[ref].loss)
            check(f"{name}_vs_{ref}", d <= BAND_LOCO,
                  f"|{res.loss!r} - {runs[ref].loss!r}| = {d!r} "
                  f"<= {BAND_LOCO}")


def one_chip() -> list[str]:
    check = Checks()
    base = BASE + ["--dp", "1"]
    runs = {
        "fp": train("fp", base + ["--sync", "fp"]),
        "loco": train("loco", base + ["--sync", "loco"]),
        "loco_kernels": train("loco_kernels",
                              base + ["--sync", "loco", "--use-kernels"]),
    }
    check_losses(check, runs, "fp")
    d = abs(runs["loco_kernels"].loss - runs["loco"].loss)
    check("kernels_vs_jnp", d <= TOL_KERNELS,
          f"|{runs['loco_kernels'].loss!r} - {runs['loco'].loss!r}| = "
          f"{d!r} <= {TOL_KERNELS}")
    n_k = runs["loco_kernels"].custom_calls
    check("kernels_compiled", n_k > 0, f"{n_k} tpu_custom_call in the step")
    check("jnp_has_no_kernels",
          runs["loco"].custom_calls == 0 and runs["fp"].custom_calls == 0,
          "loco and fp steps have no tpu_custom_call")
    return check.failed


def one_chip_state_bytes(argv: list[str]) -> int:
    """Train-state bytes the same run would hold on one chip (shapes only)."""
    import jax

    from repro.configs.base import ShapeConfig, get_arch, reduced
    from repro.launch import train as T
    from repro.launch.steps import make_init

    args = T.build_args(argv + ["--dp", "1"])
    cfg = reduced(get_arch(args.arch)) if args.reduced else get_arch(args.arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    init_fn, _ = make_init(cfg, T.make_run(args), mesh, shape)
    tree = jax.eval_shape(init_fn, jax.random.PRNGKey(args.seed))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def four_chips() -> list[str]:
    check = Checks()
    base = BASE + ["--dp", "4"]
    cfgs = {  # the baseline first, the largest program last
        "fp": ["--sync", "fp"],
        "loco": ["--sync", "loco"],
        "loco_bucket4": ["--sync", "loco", "--bucket-mb", "4"],
    }
    runs = {n: train(n, base + extra) for n, extra in cfgs.items()}
    check_losses(check, runs, "fp")
    d = abs(runs["loco_bucket4"].loss - runs["loco"].loss)
    check("bucket4_vs_monolithic", d <= TOL_BUCKET,
          f"|{runs['loco_bucket4'].loss!r} - {runs['loco'].loss!r}| = "
          f"{d!r} <= {TOL_BUCKET}")
    for name, res in runs.items():
        per_dev = res.state_bytes
        check(f"{name}_mesh_4", res.n_devices == 4 and len(per_dev) == 4,
              f"mesh of {res.n_devices} devices, state on {sorted(per_dev)}")
        one = one_chip_state_bytes(BASE + cfgs[name])
        ratios = {d: b / one for d, b in sorted(per_dev.items())}
        # fp: master weights and Adam moments are sharded four ways.  LoCo
        # also keeps each peer's own full-length f8 compensation error (one
        # byte per parameter against twelve of f32 master + moments), so a
        # device holds (12/4 + 1)/13 ~ 0.31 of the one-chip state.
        hi = 0.27 if name == "fp" else 0.35
        check(f"{name}_state_per_device",
              all(0.24 <= r <= hi for r in ratios.values()),
              f"per-device state / one-chip state {ratios} in "
              f"[0.24, {hi}]; per-device bytes {per_dev}, one chip {one}; "
              f"peak_bytes_in_use {res.peak_bytes}")
    return check.failed


def main() -> int:
    four = "--four-chips" in sys.argv[1:]
    unknown = [a for a in sys.argv[1:] if a != "--four-chips"]
    if unknown:
        return fail(f"unknown arguments {unknown}")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"the repro package is not beside this script ({ROOT})")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from repro.launch import train as T

    T.use_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        return fail(f"JAX found no TPU (platform {platform!r}); "
                    "this smoke test never falls back to the CPU")
    need = 4 if four else 1
    if len(devices) < need:
        return fail(f"needs {need} chips, JAX found {len(devices)}")
    print(f"device: {devices[0].device_kind} x{len(devices)}; "
          f"compile cache {jax.config.jax_compilation_cache_dir}", flush=True)

    failed = four_chips() if four else one_chip()
    if failed:
        return fail(f"failed checks: {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
