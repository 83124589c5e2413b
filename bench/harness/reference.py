"""The plain reference: the encoder's forward pass, masked-LM loss and
gradients in float32 ``jax.numpy`` at ``highest`` matmul precision, and
1-bit Adam (Algorithm 1 of the paper) with its exchange written as the
plain mean over workers.

It follows the architecture the program trains (``bench/configs/*.json``
lists where that departs from published BERT): token embeddings with no
learned positions, rotary positions on queries and keys (split halves,
theta ``rope_theta``), pre-norm RMSNorm blocks, full non-causal softmax
attention, a tanh-approximated GELU MLP, a final RMSNorm and an untied
output head over the vocabulary padded to a multiple of 8, its padded
columns held out of the softmax; the loss is the mean cross-entropy over
the masked positions of each worker's rows.

Parameters live in one flat float32 vector in the order of their pytree
leaves, padded to a multiple of ``workers x block``; 1-bit compression
takes one scale per ``block`` consecutive elements of it (the mean of
their magnitudes) and a sign per element (``>= 0`` is positive).

Nothing here imports the program or takes anything it made.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

B1, B2, EPS = 0.9, 0.999, 1e-8
AXIS = "w"


@dataclasses.dataclass(frozen=True)
class Model:
    layers: int
    d: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c.get("num_key_value_heads",
                                  c["num_attention_heads"]),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"])

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 8) * 8


def param_shapes(m: Model) -> Dict:
    """The parameter tree, with the layers stacked on a leading axis."""
    L, d, f, vp = m.layers, m.d, m.d_ff, m.padded_vocab
    kv = m.kv_heads * (d // m.heads)
    return {"blocks": {"l0": {
        "ffn": {"wd": (L, f, d), "wg": (L, d, f)},
        "mixer": {"wk": (L, d, kv), "wo": (L, d, d), "wq": (L, d, d),
                  "wv": (L, d, kv)},
        "norm1": (L, d), "norm2": (L, d)}},
        "embed": (vp, d), "norm_f": (d,), "w_out": (d, vp)}


def _leaf_shapes(m: Model) -> List[Tuple[int, ...]]:
    return jax.tree.leaves(param_shapes(m), is_leaf=lambda x: isinstance(
        x, tuple))


def leaf_names(m: Model) -> List[str]:
    paths = jax.tree_util.tree_flatten_with_path(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))[0]
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in paths]


def leaf_sizes(m: Model) -> List[int]:
    return [math.prod(s) for s in _leaf_shapes(m)]


def flat_length(m: Model, workers: int, block: int) -> int:
    q = workers * block
    return -(-sum(leaf_sizes(m)) // q) * q


def init_params(key, m: Model) -> Dict:
    """Weights from ``key``: norm scales 1, the embedding N(0, 0.02^2),
    every other matrix N(0, 1/fan_in), fan_in being its second-to-last
    axis; leaf ``i`` draws from ``fold_in(key, i)``."""
    names = leaf_names(m)
    leaves = []
    for i, (name, shape) in enumerate(zip(names, _leaf_shapes(m))):
        if name.rsplit("/", 1)[-1].startswith("norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        std = 0.02 if name == "embed" else shape[-2] ** -0.5
        leaves.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32) * std)
    return jax.tree.unflatten(jax.tree.structure(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple)), leaves)


def flatten(tree, d_pad: int) -> jax.Array:
    flat = jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])
    return jnp.pad(flat, (0, d_pad - flat.shape[0]))


def unflatten(flat: jax.Array, m: Model) -> Dict:
    out, off = [], 0
    for shape in _leaf_shapes(m):
        n = math.prod(shape)
        out.append(flat[off:off + n].reshape(shape))
        off += n
    return jax.tree.unflatten(jax.tree.structure(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple)), out)


# --- matrix products ---------------------------------------------------------

def mm_f32(spec: str, a, b):
    """float32 product at ``highest`` precision (full f32 on a TPU)."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def mm_bf16(spec: str, a, b):
    """A control's product: operands rounded to bfloat16, products summed
    in float32."""
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def mm_fp8(spec: str, a, b):
    """The control's product: operands rounded to float8 (e4m3), products
    summed in float32."""
    f8 = jnp.float8_e4m3fn
    return jnp.einsum(spec, a.astype(f8), b.astype(f8),
                      preferred_element_type=jnp.float32)


MATMULS: Dict[str, Callable] = {"float32": mm_f32, "bfloat16": mm_bf16,
                                 "float8": mm_fp8}
# the control of a configuration: the nearest precision below the one
# its matrix products are stated in
CONTROL = {"float32": "bfloat16", "bfloat16": "float8"}


# --- the model ---------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (b, s, h, hd); rotary positions on split halves."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], -1)


def _block(x, p, m: Model, mm):
    b, s, d = x.shape
    hd = d // m.heads
    h = _rms(x, p["norm1"], m.norm_eps)
    a = p["mixer"]
    q = mm("bsd,de->bse", h, a["wq"]).reshape(b, s, m.heads, hd)
    k, v = (mm("bsd,de->bse", h, a[w]).reshape(b, s, m.kv_heads, hd)
            for w in ("wk", "wv"))
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    # query head i reads key/value head i // (heads / kv_heads)
    k, v = (jnp.repeat(t, m.heads // m.kv_heads, axis=2) for t in (k, v))
    att = jax.nn.softmax(mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd), -1)
    o = mm("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
    x = x + mm("bsd,de->bse", o, a["wo"])
    h = _rms(x, p["norm2"], m.norm_eps)
    f = p["ffn"]
    return x + mm("bsf,fd->bsd",
                  jax.nn.gelu(mm("bsd,df->bsf", h, f["wg"]),
                              approximate=True), f["wd"])


def loss_sum(params, tokens, labels, mask, m: Model, mm):
    """Summed cross-entropy over the masked positions of these rows."""
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda x, p: (_block(x, p, m, mm), None))
    x, _ = jax.lax.scan(body, x, params["blocks"]["l0"])
    h = _rms(x, params["norm_f"], m.norm_eps)
    logits = mm("bsd,dv->bsv", h, params["w_out"])
    logits = jnp.where(jnp.arange(logits.shape[-1]) < m.vocab, logits,
                       -jnp.inf)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum((lse - ll) * mask)


def loss_and_grad(params, batch, m: Model, mm, rows: int):
    """Mean masked loss of one worker's rows and its gradient, summed
    over blocks of ``rows`` rows so that it fits."""
    tok, lab, msk = batch["tokens"], batch["labels"], batch["loss_mask"]
    denom = jnp.maximum(jnp.sum(msk), 1.0)
    n = tok.shape[0] // rows
    blocks = tuple(x.reshape((n, rows) + x.shape[1:])
                   for x in (tok, lab, msk))
    vg = jax.value_and_grad(loss_sum)

    def body(acc, blk):
        l, g = vg(params, *blk, m, mm)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (lsum, gsum), _ = jax.lax.scan(body, zero, blocks)
    return lsum / denom, jax.tree.map(lambda g: g / denom, gsum)


# --- 1-bit Adam --------------------------------------------------------------

def onebit(x: jax.Array, block: int) -> jax.Array:
    """sign(x) (>= 0 positive) times the mean magnitude of x's block."""
    xb = x.reshape(-1, block)
    scale = jnp.mean(jnp.abs(xb), axis=1, keepdims=True)
    return jnp.where(xb >= 0, scale, -scale).reshape(-1)


@dataclasses.dataclass(frozen=True)
class Plan:
    """How one reference run is computed.  ``precision`` and ``fault``
    hold in the ``stages`` named, and the plain float32 reference in the
    others.  ``fault`` plants one of the faults the comparison must
    catch: ``half_batch`` (each worker's loss is the mean over the first
    half of its rows)."""
    model: Model
    workers: int
    block: int
    rows: int              # rows per gradient block
    precision: str = "float32"
    fault: str = ""
    stages: Tuple[str, ...] = ("warmup", "compressed")


def _step(x, m_, v, werr, serr, batch, lr, *, plan: Plan, stage: str,
          d_real: int):
    mo, n = plan.model, plan.workers
    planted = stage in plan.stages
    mm = MATMULS[plan.precision if planted else "float32"]
    if planted and plan.fault == "half_batch":
        half = batch["loss_mask"].shape[0] // 2
        batch = dict(batch, loss_mask=batch["loss_mask"].at[half:].set(0.0))
    d_pad = x.shape[0]
    loss, g = loss_and_grad(unflatten(x, mo), batch, mo, mm, plan.rows)
    g = flatten(g, d_pad)
    loss = jax.lax.pmean(loss, AXIS)
    if stage == "warmup":
        g = jax.lax.pmean(g, AXIS)
        m_ = B1 * m_ + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        upd = m_ / (jnp.sqrt(v) + EPS)
    else:
        buf = B1 * m_ + (1 - B1) * g + werr[0]
        c = onebit(buf, plan.block)
        werr = (buf - c)[None]
        avg = jax.lax.psum(c, AXIS) / n
        chunk = d_pad // n
        sbuf = jax.lax.dynamic_slice(
            avg, (jax.lax.axis_index(AXIS) * chunk,), (chunk,)) + serr[0]
        sc = onebit(sbuf, plan.block)
        serr = (sbuf - sc)[None]
        m_ = jax.lax.all_gather(sc, AXIS, tiled=True)
        upd = m_ / (jnp.sqrt(v) + EPS)
        g = jax.lax.pmean(g, AXIS)
    x = x - lr * upd
    # the padding past the last parameter holds no parameter: zero
    x = jnp.where(jnp.arange(d_pad) < d_real, x, 0.0)
    return x, m_, v, werr, serr, loss[None], g


class Reference:
    """Runs the reference steps on ``devices`` (one worker each)."""

    def __init__(self, plan: Plan, devices):
        self.plan = plan
        self.mesh = Mesh(np.array(devices[:plan.workers]), (AXIS,))
        self.d_real = sum(leaf_sizes(plan.model))
        self.d_pad = flat_length(plan.model, plan.workers, plan.block)
        rep, row = P(), P(AXIS)
        specs = (rep, rep, rep, row, row, row, rep)
        self._steps = {}
        for stage in ("warmup", "compressed"):
            fn = partial(_step, plan=plan, stage=stage, d_real=self.d_real)
            mapped = jax.shard_map(fn, mesh=self.mesh, in_specs=specs,
                                   out_specs=(rep, rep, rep, row, row, row,
                                              rep),
                                   check_vma=False)
            self._steps[stage] = jax.jit(mapped, donate_argnums=(0, 1, 2, 3,
                                                                 4))

    def sharding(self, spec=P()) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def init(self, key) -> Dict[str, jax.Array]:
        """Flat weights from ``key`` and zero optimizer state."""
        n, d = self.plan.workers, self.d_pad
        mo = self.plan.model
        rep, row = self.sharding(), self.sharding(P(AXIS))
        x = jax.jit(lambda k: flatten(init_params(k, mo), d),
                    out_shardings=rep)(key)
        z = partial(jnp.zeros, dtype=jnp.float32)
        return {"x": x, "m": jax.jit(lambda: z((d,)), out_shardings=rep)(),
                "v": jax.jit(lambda: z((d,)), out_shardings=rep)(),
                "werr": jax.jit(lambda: z((n, d)), out_shardings=row)(),
                "serr": jax.jit(lambda: z((n, d // n)),
                                out_shardings=row)()}

    def step(self, state, stage: str, batch, lr: float):
        """One step; returns the new state, the mean worker loss and the
        gradient the optimizer got (the mean over the workers)."""
        b = {k: jax.device_put(v, self.sharding(P(AXIS)))
             for k, v in batch.items()}
        x, m_, v, werr, serr, loss, g = self._steps[stage](
            state["x"], state["m"], state["v"], state["werr"],
            state["serr"], b, jnp.float32(lr))
        return ({"x": x, "m": m_, "v": v, "werr": werr, "serr": serr},
                float(np.asarray(loss)[0]), g)
