"""The parts of the plain float32 reference that every architecture
shares, importing nothing of the program: the TimeFloats E4M4 scalar
product, the norms and rotations, the loss, AdamW over the check steps,
and the norms that the check compares. Each architecture's own forward
pass, sizes, weights plan and work count live in ``archs/<name>.py``,
which the configuration file names under ``"reference"``.

Every projection matmul of a reference is a TimeFloats E4M4 scalar
product (``tf_matmul``):

1. each operand is scaled by a power of two so that its largest magnitude
   has exponent 7 (stored code 14 of the 4-bit exponent, bias 7);
2. each element is rounded to E4M4 (4-bit mantissa, ties to even, values
   under the exponent range flushed to zero, over it saturated);
3. along the contracted dim, in blocks of 64, each element's 5-bit
   significand is shifted right (floor) to the block's largest exponent,
   and dropped once the shift passes 4 bits;
4. the aligned integers are multiplied and summed exactly in float32 and
   the two power-of-two scales are removed.

Everything else runs in float32 at ``highest`` matmul precision. The
control is the reference one precision step below the configuration:
``man_bits=3``, E4M3 scalar products where the configuration states E4M4.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 64
MAN_BITS = 4
BIAS = 7
MAX_CODE = 15
TOP_EXP = MAX_CODE - 1 - BIAS       # the prescale puts amax at 2^7


def _pow2_scale(a):
    amax = jnp.max(jnp.abs(a))
    e = jnp.floor(jnp.log2(jnp.maximum(amax, 1e-30)))
    return jnp.where(amax > 0, jnp.exp2(TOP_EXP - e), 1.0)


def _e4m4_fields(x, man_bits: int):
    """(stored exponent code, significand, sign, nonzero) of x."""
    mant, ex = jnp.frexp(x)                       # |mant| in [0.5, 1)
    sig = jnp.abs(mant) * 2.0                     # [1, 2)
    unb = ex - 1
    m = jnp.round((sig - 1.0) * (1 << man_bits))  # ties to even
    carry = m >= (1 << man_bits)
    m = jnp.where(carry, 0.0, m)
    code = unb + carry.astype(unb.dtype) + BIAS
    nonzero = (code >= 0) & (x != 0)
    over = code > MAX_CODE
    code = jnp.clip(code, 0, MAX_CODE)
    m = jnp.where(over, (1 << man_bits) - 1, m)
    significand = (m + (1 << man_bits)).astype(jnp.int32)
    return (jnp.where(nonzero, code, 0), jnp.where(nonzero, significand, 0),
            jnp.where(x < 0, -1, 1), nonzero)


def aligned(a, axis: int, man_bits: int = MAN_BITS):
    """The values the E4M4 block-aligned operand ``a`` stands for, with
    blocks of 64 along ``axis`` (the contracted dim). Returns (values, s):
    the values carry the power-of-two prescale ``s``."""
    s = _pow2_scale(a)
    a = jnp.moveaxis(a * s, axis, -1)
    k = a.shape[-1]
    assert k % BLOCK == 0, "contracted dims are multiples of 64 here"
    code, sig, sign, nz = _e4m4_fields(
        a.reshape(a.shape[:-1] + (k // BLOCK, BLOCK)), man_bits)
    top = jnp.maximum(jnp.max(jnp.where(nz, code, -(2 ** 30)), axis=-1,
                              keepdims=True), 0)
    shift = top - code
    q = jnp.where(shift > man_bits, 0, sig >> jnp.clip(shift, 0, 31))
    vals = (sign * q).astype(jnp.float32) * jnp.exp2(
        (top - BIAS - man_bits).astype(jnp.float32))
    return jnp.moveaxis(vals.reshape(a.shape), -1, axis), s


def tf_matmul(x, w, man_bits: int = MAN_BITS):
    """x (..., K) @ w (K, N) as a TimeFloats scalar product. Its gradient
    is the crossbar's too: dx reads the forward's aligned weight against
    the gradient aligned in blocks along N; dW reads the forward's aligned
    input against the gradient aligned in blocks along the rows."""
    y = _tf2(x.reshape(-1, x.shape[-1]), w, man_bits)
    return y.reshape(x.shape[:-1] + (w.shape[-1],))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _tf2(x, w, man_bits):
    return _tf2_fwd(x, w, man_bits)[0]


def _tf2_fwd(x, w, man_bits):
    xa, sx = aligned(x, 1, man_bits)
    wa, sw = aligned(w, 0, man_bits)
    return _dot(xa, wa, ((1,), (0,))) / (sx * sw), (xa, sx, wa, sw)


def _tf2_bwd(man_bits, res, g):
    xa, sx, wa, sw = res
    g_n, sg = aligned(g, 1, man_bits)       # blocks along N, per row
    g_m, _ = aligned(g, 0, man_bits)        # blocks along the rows
    dx = _dot(g_n, wa, ((1,), (1,))) / (sg * sw)
    dw = _dot(xa, g_m, ((0,), (0,))) / (sx * sg)
    return dx, dw


_tf2.defvjp(_tf2_fwd, _tf2_bwd)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta: float):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params, batch, forward, spec: dict, man_bits: int = MAN_BITS):
    """Mean next-token negative log-likelihood over the masked positions,
    of the logits an architecture's ``forward`` gives."""
    tok = batch["tokens"]
    logits = forward(params, tok, jnp.ones(tok.shape, jnp.float32), spec,
                     man_bits=man_bits, remat=True)
    lse = jax.nn.logsumexp(logits, -1)
    lab = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum((lse - lab) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _lr(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then a cosine down
    to ``min_lr_ratio`` of it at ``total_steps``."""
    lr, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    lo = opt["min_lr_ratio"]
    return lr * (lo + (1 - lo) * 0.5 * (1 + math.cos(math.pi * frac)))


def leaf_norms(tree, rows=None):
    """The 2-norm of each leaf. With ``rows``, a (V,) mask over the
    vocabulary, a leaf whose leading dim is V (a table the tokens index
    by row) is taken over the marked rows only."""
    def one(a):
        a = a.astype(jnp.float32)
        if rows is not None and a.ndim and a.shape[0] == rows.shape[0]:
            a = a * rows.astype(jnp.float32).reshape(
                (-1,) + (1,) * (a.ndim - 1))
        return jnp.sqrt(jnp.sum(jnp.square(a)))

    return jax.tree.map(one, tree)


def present_rows(batches, vocab: int):
    """(V,) mask of the token ids that ``batches`` hold, as inputs or as
    labels."""
    seen = np.zeros(vocab, bool)
    for b in batches:
        for k in ("tokens", "labels"):
            seen[np.asarray(b[k]).ravel()] = True
    return seen


@jax.jit
def change_norms(new, old, rows):
    """Per-leaf norms of ``new - old``: a token-indexed table's over the
    rows of the tokens present (``rows``), and apart, over the rest."""
    d = jax.tree.map(jnp.subtract, new, old)
    return leaf_norms(d, rows), leaf_norms(d, ~rows)


def train_steps(make_params, batches, forward, spec: dict, opt: dict,
                rows, man_bits: int = MAN_BITS):
    """AdamW over ``batches`` from ``make_params()``, the model being an
    architecture's ``forward`` at the sizes ``spec``, with the gradient
    clipped to a global norm of ``grad_clip`` first. Returns the losses,
    the per-leaf norms of the first (clipped) gradient, and the per-leaf
    norms of the parameters' change over all the steps, a token-indexed
    table's over the rows ``rows`` marks and apart over the rest (the
    starting parameters are made again for that, rather than kept)."""
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: loss(p, b, forward, spec, man_bits)))

    @jax.jit
    def update(p, m, v, g, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9)), g)
        m = jax.tree.map(lambda a, b: opt["b1"] * a + (1 - opt["b1"]) * b,
                         m, g)
        v = jax.tree.map(lambda a, b: opt["b2"] * a
                         + (1 - opt["b2"]) * b * b, v, g)
        bc1, bc2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        p = jax.tree.map(lambda a, mm, vv: a - lr * (mm / bc1) / (
            jnp.sqrt(vv / bc2) + opt["eps"]), p, m, v)
        return p, m, v, leaf_norms(g)

    p = make_params()
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            l, g = grad(p, b)
            p, m, v, gn = update(p, m, v, g, float(i + 1), _lr(opt, i))
            del g
            losses.append(float(l))
            if first is None:
                first = jax.device_get(gn)
    del m, v
    change, rest = jax.device_get(change_norms(p, make_params(),
                                               jnp.asarray(rows)))
    return losses, first, change, rest
