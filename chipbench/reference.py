"""Plain reference of a training cell's first steps, in float32.

It imports nothing of the program.  From the seed's weights
(``model.make_weights``) and batches (``data.make_ring``) it follows the
first ``STEPS`` optimizer steps of the cell as the configuration states
them:

* the decoder's loss and gradients, one row at a time, in straightforward
  ``jax.numpy`` at ``Precision.HIGHEST`` (RMSNorm, rotary embedding,
  grouped-query causal attention with the sliding window, SwiGLU MLP,
  untied or tied head, MiniCPM's embedding/residual/logit scales);
* the gradient sync: the mean over data-parallel ranks, and for a LoCo
  wire each rank's error-compensated 4-bit block quantization with its
  f8 error state (LoCo paper, Algorithm 1: Eqns. 2, 3, 5, 7), decoded and
  averaged, per microbatch;
* global-norm clipping and AdamW under a linear warmup of the learning
  rate.

It returns the per-step losses, the per-leaf norms of the first step's
clipped gradient, and the per-leaf norms of the parameters' change over
the steps.  ``precision="f8"`` is the control: every matrix product takes
its operands, and its cotangent, through a per-tensor scaled float8_e4m3
round trip (the step below the bfloat16 the configuration computes in).
``fault`` plants one of the faults that the comparison must catch.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.model import NORMS, Dims, make_weights

STEPS = 3
HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


# ---------------------------------------------------------------------------
# precision of the matrix products
# ---------------------------------------------------------------------------

def _f8_round(x):
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


@jax.custom_vjp
def _q8(x):
    return _f8_round(x)


_q8.defvjp(lambda x: (_f8_round(x), None), lambda _, g: (_f8_round(g),))


def _operand(x, precision: str):
    return _q8(x) if precision == "f8" else x


def _mm(a, b, precision):
    return jnp.matmul(_operand(a, precision), _operand(b, precision),
                      precision=HIGHEST)


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# the model, one row at a time
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def row_loss(params, toks, dims: Dims, precision: str = "f32"):
    """Mean next-token cross entropy of one row of ``S + 1`` tokens."""
    inp, tgt = toks[:-1], toks[1:]
    S = inp.shape[0]
    H, KV, hd = dims.heads, dims.kv_heads, dims.hd
    x = params["tok"][inp] * dims.scale_emb
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if dims.window:
        mask &= pos[None, :] > pos[:, None] - dims.window

    def layer(x, p):
        h = _rms(x, p["norm1"], dims.eps)
        q = _rope(_mm(h, p["wq"], precision).reshape(S, H, hd), dims.rope_theta)
        k = _rope(_mm(h, p["wk"], precision).reshape(S, KV, hd), dims.rope_theta)
        v = _mm(h, p["wv"], precision).reshape(S, KV, hd)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = _einsum("qhd,khd->hqk", q, k, precision) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = _einsum("hqk,khd->qhd", a, v, precision).reshape(S, H * hd)
        x = x + dims.residual_scale * _mm(o, p["wo"], precision)
        h = _rms(x, p["norm2"], dims.eps)
        m = jax.nn.silu(_mm(h, p["w1"], precision)) * _mm(h, p["w3"], precision)
        return x + dims.residual_scale * _mm(m, p["w2"], precision), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = _rms(x, params["norm_f"], dims.eps)
    w = params["tok"].T if dims.tied else params["head"]
    logits = _mm(x, w, precision) * dims.logit_scale
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, tgt[:, None], -1)[:, 0])


# ---------------------------------------------------------------------------
# the LoCo wire (4-bit block absmax codes, f8 compensation error)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Wire:
    strategy: str          # "fp" | "loco"
    bits: int = 4
    block: int = 256
    beta: float = 0.5
    error_scale: float = 2.0**14
    min_numel: int = 2**16

    @staticmethod
    def from_traffic(t: dict) -> "Wire":
        s = t["sync"]
        return Wire(strategy=s["strategy"], bits=s.get("bits", 4),
                    block=s.get("block", 256), beta=s.get("beta", 0.5),
                    error_scale=s.get("error_scale", 2.0**14),
                    min_numel=s.get("min_numel", 2**16))


def _compressed(wire: Wire, name: str, shape) -> bool:
    per = math.prod(shape[1:]) if name.startswith("layers/") else math.prod(shape)
    return wire.strategy == "loco" and per >= wire.min_numel


def _blocks(x, stacked: bool, block: int):
    """(L?, ...) -> (L?, nblocks, block): the flat row-major tensor of each
    layer, zero-padded to whole blocks."""
    lead = x.shape[:1] if stacked else ()
    flat = x.reshape(*lead, -1)
    pad = -flat.shape[-1] % block
    flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, pad)])
    return flat.reshape(*lead, -1, block)


def _unblocks(xb, like):
    lead = like.shape[:1] if xb.ndim == 3 else ()
    flat = xb.reshape(*lead, -1)[..., :math.prod(like.shape[len(lead):])]
    return flat.reshape(like.shape)


def loco_encode(g, e8, wire: Wire):
    """One rank's compress of one blocked leaf: (decoded d, new f8 error)."""
    qmax = 2 ** (wire.bits - 1) - 1
    e = e8.astype(jnp.float32) / wire.error_scale
    h = g + e                                                    # Eqn. 2
    absmax = jnp.max(jnp.abs(h), axis=-1, keepdims=True)
    scale = qmax / jnp.maximum(absmax, 1e-30)
    d = jnp.clip(jnp.round(h * scale), -qmax - 1, qmax) / scale  # Eqn. 3
    e_new = (1 - wire.beta) * e + wire.beta * (h - d)            # Eqn. 5
    e_new = jnp.clip(e_new * wire.error_scale, -F8_MAX, F8_MAX)  # Eqn. 7
    return d, e_new.astype(jnp.float8_e4m3fn)


def _owner_mask(shape_blocked, stacked: bool, ranks: int, block: int, r):
    """1 where rank ``r`` owns the element of the FSDP layout (each leaf's
    flat vector padded to ``ranks * 512`` and cut in ``ranks`` chunks)."""
    nb = shape_blocked[-2]
    n = nb * block
    pad = -(-n // (ranks * 512)) * ranks * 512
    idx = jnp.arange(n).reshape(nb, block)
    return (idx // (pad // ranks) == r).astype(jnp.float32)


# ---------------------------------------------------------------------------
# one step
# ---------------------------------------------------------------------------

def _flat_items(tree):
    out = {n: v for n, v in tree.items() if n != "layers"}
    out.update({f"layers/{n}": v for n, v in tree["layers"].items()})
    return out


def _unflat(items):
    out = {n: v for n, v in items.items() if not n.startswith("layers/")}
    out["layers"] = {n.split("/", 1)[1]: v for n, v in items.items()
                     if n.startswith("layers/")}
    return out


def leaf_norms(tree) -> dict:
    """Per-leaf L2 norms; a stacked leaf gives one per layer."""
    out = {}
    for n, v in _flat_items(tree).items():
        axes = tuple(range(1, v.ndim)) if n.startswith("layers/") else None
        out[n] = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)), axis=axes))
    return out


@dataclasses.dataclass(frozen=True)
class Setting:
    dims: Dims
    wire: Wire
    ranks: int              # data-parallel ranks
    micro: int              # rows per rank per microbatch
    opt: dict               # lr, b1, b2, eps, weight_decay, clip_norm
    precision: str = "f32"
    fault: str | None = None   # None | "half" | "exchange"


def _rank_rows(rows, s: Setting):
    """(B, S+1) -> (accum, ranks, micro, S+1): the FSDP runtime's order
    (rank r holds rows [r*B/ranks, (r+1)*B/ranks), in microbatches)."""
    B = rows.shape[0]
    lb = B // s.ranks
    accum = lb // s.micro
    if s.fault == "half":
        rows = jnp.concatenate([rows[:B // 2], rows[:B // 2]])
    x = rows.reshape(s.ranks, accum, s.micro, -1)
    return x.transpose(1, 0, 2, 3)


def _micro_grad(params, mrows, s: Setting):
    """Mean loss and gradient over ``micro`` rows of one rank."""
    vg = jax.value_and_grad(functools.partial(row_loss, dims=s.dims,
                                              precision=s.precision))
    if mrows.shape[0] == 1:
        return vg(params, mrows[0])

    def one(acc, toks):
        loss, g = vg(params, toks)
        return jax.tree.map(jnp.add, acc, (loss, g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (loss, g), _ = jax.lax.scan(one, zero, mrows)
    return loss / s.micro, jax.tree.map(lambda a: a / s.micro, g)


def step_grads(params, rows, errs, s: Setting):
    """The synced, microbatch-averaged gradient of one step.

    Microbatch by microbatch, each rank's gradient goes through its codec
    (the LoCo error state moves on once per microbatch, as in the FSDP
    runtime) and the decoded contributions are averaged over ranks and
    microbatches into one accumulator.
    """
    x = _rank_rows(rows, s)
    accum = x.shape[0]
    x = x.reshape(accum * s.ranks, *x.shape[2:])
    shapes = {n: v.shape for n, v in _flat_items(params).items()}
    w = 1.0 / (accum * s.ranks)

    def one(carry, rx):
        errs, acc, lsum, i = carry
        r = i % s.ranks
        loss, g = _micro_grad(params, rx, s)
        gi = _flat_items(g)
        errs, out = dict(errs), {}
        for n, gn in gi.items():
            if n in errs:
                stacked = n.startswith("layers/")
                d, e = loco_encode(_blocks(gn, stacked, s.wire.block),
                                   errs[n][r], s.wire)
                errs[n] = errs[n].at[r].set(e)
                if s.fault == "exchange":
                    d = d * s.ranks * _owner_mask(d.shape, stacked, s.ranks,
                                                  s.wire.block, r)
                gn = _unblocks(d, gn)
            out[n] = acc[n] + w * gn
        return (errs, out, lsum + loss, i + 1), None

    zero = {n: jnp.zeros(shp, jnp.float32) for n, shp in shapes.items()}
    (errs, acc, lsum, _), _ = jax.lax.scan(one, (errs, zero, 0.0, 0), x)
    return _unflat(acc), errs, lsum * w


def adamw(params, grads, m, v, t, s: Setting):
    o = s.opt
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    c = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * c, grads)
    m = jax.tree.map(lambda m_, g: o["b1"] * m_ + (1 - o["b1"]) * g, m, grads)
    v = jax.tree.map(lambda v_, g: o["b2"] * v_ + (1 - o["b2"]) * g * g, v, grads)
    bc1, bc2 = 1 - o["b1"] ** (t + 1), 1 - o["b2"] ** (t + 1)
    lr = o["lr"] * jnp.minimum(1.0, (t + 1) / max(o["warmup"], 1))

    def upd(name, p, m_, v_):
        u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + o["eps"])
        if name.split("/")[-1] not in NORMS:
            u = u + o["weight_decay"] * p
        return p - lr * u

    new = _unflat({n: upd(n, p, m_, v_) for (n, p), m_, v_ in zip(
        _flat_items(params).items(), _flat_items(m).values(),
        _flat_items(v).values())})
    return new, m, v, grads


# ---------------------------------------------------------------------------
# placement and the run
# ---------------------------------------------------------------------------

def _spec(name: str, shape, n_dev: int, ax: int | None = None) -> P:
    """Shard a leaf over the devices along its first dimension after the
    layer axis (so a codec block stays on one device); norms and uneven
    leaves stay whole on each."""
    if ax is None:
        ax = 1 if name.startswith("layers/") else 0
    if n_dev == 1 or name.split("/")[-1] in NORMS or shape[ax] % n_dev:
        return P()
    return P(*([None] * ax + ["x"]))


@dataclasses.dataclass
class Program:
    """The reference's jitted pieces for one setting and set of devices."""

    init: object
    zeros: object
    init_errs: object
    step: object
    change: object
    rep: NamedSharding
    p_shard: dict
    e_shard: dict
    err_shapes: dict


def build(s: Setting, devices) -> Program:
    """Jit the reference's pieces with its state spread over ``devices``."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices), ("x",))
    n_dev = len(devices)
    rep = NamedSharding(mesh, P())
    shapes = _flat_items(s.dims.shapes())
    p_shard = _unflat({n: NamedSharding(mesh, _spec(n, shp, n_dev))
                       for n, shp in shapes.items()})

    def err_shape(n):
        shp = shapes[n]
        stacked = n.startswith("layers/")
        per = math.prod(shp[1:]) if stacked else math.prod(shp)
        return ((s.ranks,) + (shp[:1] if stacked else ())
                + (-(-per // s.wire.block), s.wire.block))

    err_shapes = {n: err_shape(n) for n, shp in shapes.items()
                  if _compressed(s.wire, n, shp)}
    e_shard = {n: NamedSharding(mesh, _spec(
        n, shp, n_dev, ax=2 if n.startswith("layers/") else 1))
        for n, shp in err_shapes.items()}

    @functools.partial(jax.jit, out_shardings=p_shard)
    def init(key):
        return make_weights(key, s.dims)

    @functools.partial(jax.jit, out_shardings=p_shard)
    def zeros():
        return jax.tree.map(lambda shp: jnp.zeros(shp, jnp.float32),
                            s.dims.shapes(),
                            is_leaf=lambda x: isinstance(x, tuple))

    @functools.partial(jax.jit, out_shardings=e_shard)
    def init_errs():
        return {n: jnp.zeros(shp, jnp.float8_e4m3fn)
                for n, shp in err_shapes.items()}

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3, 4),
                       out_shardings=(p_shard, e_shard, p_shard, p_shard,
                                      rep, rep))
    def step(params, rows, errs, m, v, t):
        with jax.default_matmul_precision("highest"):
            grads, errs, loss = step_grads(params, rows, errs, s)
            params, m, v, clipped = adamw(params, grads, m, v, t, s)
        return params, errs, m, v, loss, leaf_norms(clipped)

    @functools.partial(jax.jit, out_shardings=rep)
    def change(params, key):
        w0 = make_weights(key, s.dims)
        return leaf_norms(jax.tree.map(jnp.subtract, params, w0))

    return Program(init=init, zeros=zeros, init_errs=init_errs, step=step,
                   change=change, rep=rep, p_shard=p_shard, e_shard=e_shard,
                   err_shapes=err_shapes)


def follow(seed_key, ring, s: Setting, devices) -> dict:
    """Run the reference over the ring's first ``STEPS`` batches.

    ``devices`` are the chips it may spread its state over (the cell's
    own).  Returns host readings: ``losses`` (one per step), ``grad`` and
    ``change`` (per-leaf norms, see :func:`leaf_norms`).
    """
    pr = build(s, devices)
    params = pr.init(seed_key)
    m, v = pr.zeros(), pr.zeros()
    errs = pr.init_errs()
    losses, g0 = [], None
    for t in range(STEPS):
        rows = jax.device_put(ring[t], pr.rep)
        params, errs, m, v, loss, gn = pr.step(params, rows, errs, m, v,
                                               jnp.float32(t))
        losses.append(float(loss))
        if t == 0:
            g0 = jax.device_get(gn)
    del m, v, errs
    ch = jax.device_get(pr.change(params, seed_key))
    return {"losses": losses, "grad": g0, "change": ch}
