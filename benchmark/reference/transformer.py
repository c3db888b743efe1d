"""Plain float32 reference of the post-LN Vaswani encoder-decoder.

Straight ``jax.numpy``: no kernels, no cache, no batching tricks, and no
import of the program under test. It follows "Attention Is All You Need"
(arXiv:1706.03762) in the variant the program implements; each departure
from the paper is the program's and is named here:

- no ``sqrt(d_model)`` scaling of the embeddings, separate source, target
  and output tables (the paper ties the three);
- fused ``qkv`` / ``kv`` projection matrices (same mathematics);
- LayerNorm epsilon 1e-6, sinusoids with even channels sin and odd cos.

Everything is computed under ``jax.default_matmul_precision("highest")`` by
the callers in this package (``precise``), because a float32 product on a
TPU otherwise runs in bfloat16 passes.

``matmul`` is a parameter of every function so that the *control* of the
``correct`` decision can put a lower precision in its place
(``lowp_matmul``): the reference in int8, standing where the program
stands.

Dropout: training compares the program's first steps, which run with the
configuration's dropout on. The masks are a function of the step's key and
of where in the model a mask is drawn; ``DropoutMasks`` derives the same
keys the program's framework (Flax) derives - a SHA-1 of the module path
and a per-module call count folded into the step key - and draws the mask
with ``jax.random.bernoulli``. ``tests/benchmark`` pins that agreement.
"""

from __future__ import annotations

import contextlib
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
LN_EPS = 1e-6


def precise():
    """Context in which float32 matrix products are float32 on every backend."""
    return jax.default_matmul_precision("highest")


def f32_matmul(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _fake_quant_int8(t, axis):
    """Symmetric absmax int8 along ``axis`` (one scale per row/column),
    dequantised back to float32: what an int8 matrix unit would see."""
    amax = jnp.max(jnp.abs(t), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(t / scale), -127, 127) * scale


def _fake_quant_fp8(t, axis):
    """float8 e4m3 (3 bits of mantissa) with one absmax scale per row/column
    onto the format's largest finite value, 448."""
    amax = jnp.max(jnp.abs(t), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _low_precision_matmul(quant):
    """A product whose operands are rounded by ``quant`` (per-row scales on
    the left, per-column on the right) and accumulated in float32. The two
    products of its backward pass are rounded the same way; the rounding
    itself passes gradients straight through."""

    def product(x, w):
        return jnp.matmul(
            quant(x, axis=-1), quant(w, axis=-2),
            precision=jax.lax.Precision.HIGHEST,
        )

    @jax.custom_vjp
    def matmul(x, w):
        return product(x, w)

    def fwd(x, w):
        return product(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        dx = product(g, jnp.swapaxes(w, -1, -2))
        if w.ndim == 2:
            dw = product(
                x.reshape(-1, x.shape[-1]).T, g.reshape(-1, g.shape[-1])
            )
        else:
            dw = product(jnp.swapaxes(x, -1, -2), g)
        return dx, dw

    matmul.defvjp(fwd, bwd)
    return matmul


#: The control's products: the reference in the nearest precisions below
#: the bfloat16 the configurations state, standing where the program stands.
lowp_matmul = _low_precision_matmul(_fake_quant_int8)
fp8_matmul = _low_precision_matmul(_fake_quant_fp8)


def sinusoids(length: int, dim: int) -> np.ndarray:
    pos = np.arange(length, dtype=np.float32)[:, None]
    div = np.exp(
        np.arange(0, dim, 2, dtype=np.float32) * (-math.log(10000.0) / dim)
    )
    table = np.zeros((length, dim), np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div[: dim // 2])
    return table


class DropoutMasks:
    """Dropout as the program's framework draws it, from the step's key.

    ``rate == 0`` or ``key is None`` makes every call the identity.
    """

    def __init__(self, key, rate: float):
        self.key = key
        self.rate = float(rate)
        self._counts: dict[tuple, int] = {}

    @staticmethod
    def _fold(key, path: tuple):
        m = hashlib.sha1()
        for x in path:
            if isinstance(x, str):
                m.update(x.encode("utf-8"))
            else:
                m.update(x.to_bytes((x.bit_length() + 7) // 8, "big"))
        h = int.from_bytes(m.digest()[:4], "big")
        return jax.random.fold_in(key, jnp.uint32(h))

    def __call__(self, path: tuple, x):
        if self.key is None or self.rate == 0.0:
            return x
        n = self._counts.get(path, 0) + 1
        self._counts[path] = n
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(
            self._fold(self.key, path + (n,)), p=keep, shape=x.shape
        )
        return jnp.where(mask, x / keep, 0.0)


def _dense(p, x, matmul):
    return matmul(x, p["kernel"]) + p["bias"]


def _layer_norm(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _attend(q, k, v, mask, heads: int, matmul):
    """``q [B,Sq,d]``, ``k/v [B,Sk,d]``, ``mask`` broadcastable to
    ``[B,1,Sq,Sk]`` (True = may attend)."""
    b, sq, d = q.shape
    sk = k.shape[1]
    dh = d // heads
    qh = q.reshape(b, sq, heads, dh).transpose(0, 2, 1, 3)
    kh = k.reshape(b, sk, heads, dh).transpose(0, 2, 1, 3)
    vh = v.reshape(b, sk, heads, dh).transpose(0, 2, 1, 3)
    scores = matmul(qh, kh.transpose(0, 1, 3, 2)) / math.sqrt(dh)
    scores = jnp.where(mask, scores, NEG)
    weights = jax.nn.softmax(scores, axis=-1)
    out = matmul(weights, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, sq, d)


def _self_attention(p, x, mask, heads, matmul):
    q, k, v = jnp.split(_dense(p["qkv"], x, matmul), 3, axis=-1)
    return _dense(p["out"], _attend(q, k, v, mask, heads, matmul), matmul)


def _cross_attention(p, y, memory, mask, heads, matmul):
    q = _dense(p["q"], y, matmul)
    k, v = jnp.split(_dense(p["kv"], memory, matmul), 2, axis=-1)
    return _dense(p["out"], _attend(q, k, v, mask, heads, matmul), matmul)


def _ffn(p, x, drop, path, matmul):
    h = jax.nn.relu(_dense(p["up"], x, matmul))
    h = drop(path + ("ffn", "Dropout_0"), h)
    return _dense(p["down"], h, matmul)


def _embed(p, tokens, drop, path):
    table = p["embed"]["embed"]["embedding"]
    x = table[tokens] + sinusoids(tokens.shape[1], table.shape[1])
    return drop(path + ("embed", "Dropout_0"), x)


def encode(params, cfg, src, drop=None, matmul=f32_matmul):
    drop = drop or DropoutMasks(None, 0.0)
    p = params["encoder"]
    heads = cfg["num_heads"]
    key_ok = (src != cfg["pad_id"])[:, None, None, :]
    x = _embed(p, src, drop, ("encoder",))
    for i in range(cfg["num_layers"]):
        lp, path = p[f"layer_{i}"], ("encoder", f"layer_{i}")
        a = _self_attention(lp["self_attn"], x, key_ok, heads, matmul)
        x = _layer_norm(lp["ln1"], x + drop(path + ("Dropout_0",), a))
        f = _ffn(lp["ffn"], x, drop, path, matmul)
        x = _layer_norm(lp["ln2"], x + drop(path + ("Dropout_0",), f))
    return x


def decode(params, cfg, memory, src, trg_in, drop=None, matmul=f32_matmul):
    """Teacher-forced decoder pass: logits ``[B, T, V]`` for the next token
    at every position of ``trg_in``."""
    drop = drop or DropoutMasks(None, 0.0)
    p = params["decoder"]
    heads = cfg["num_heads"]
    t = trg_in.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    self_ok = causal & (trg_in != cfg["pad_id"])[:, None, None, :]
    mem_ok = (src != cfg["pad_id"])[:, None, None, :]
    y = _embed(p, trg_in, drop, ("decoder",))
    for i in range(cfg["num_layers"]):
        lp, path = p[f"layer_{i}"], ("decoder", f"layer_{i}")
        a = _self_attention(lp["self_attn"], y, self_ok, heads, matmul)
        y = _layer_norm(lp["ln1"], y + drop(path + ("Dropout_0",), a))
        c = _cross_attention(
            lp["cross_attn"], y, memory, mem_ok, heads, matmul
        )
        y = _layer_norm(lp["ln2"], y + drop(path + ("Dropout_0",), c))
        f = _ffn(lp["ffn"], y, drop, path, matmul)
        y = _layer_norm(lp["ln3"], y + drop(path + ("Dropout_0",), f))
    return _dense(params["lm_head"], y, matmul)


def forward(params, cfg, src, trg_in, drop=None, matmul=f32_matmul):
    memory = encode(params, cfg, src, drop, matmul)
    return decode(params, cfg, memory, src, trg_in, drop, matmul)


def token_loss_sum(params, cfg, src, trg, drop=None, matmul=f32_matmul):
    """Sum of the cross-entropies of the target positions that count
    (label != pad) and their number, for ``trg [B, T+1]``."""
    logits = forward(params, cfg, src, trg[:, :-1], drop, matmul)
    labels = trg[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    counted = labels != cfg["pad_id"]
    return jnp.sum(jnp.where(counted, nll, 0.0)), jnp.sum(counted)


# -- training: loss, gradients and Adam, in blocks of rows ------------------


class _SlicedDropout(DropoutMasks):
    """Masks drawn for the full batch shape, rows ``start:start+rows`` of
    them: a block sees exactly the rows of the mask that the program's
    full-batch step drew."""

    def __init__(self, key, rate, full_rows, start, rows):
        super().__init__(key, rate)
        self._full, self._start, self._rows = full_rows, start, rows

    def __call__(self, path, x):
        if self.key is None or self.rate == 0.0:
            return x
        n = self._counts.get(path, 0) + 1
        self._counts[path] = n
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(
            self._fold(self.key, path + (n,)), p=keep,
            shape=(self._full,) + x.shape[1:],
        )
        mask = jax.lax.dynamic_slice_in_dim(mask, self._start, self._rows, 0)
        return jnp.where(mask, x / keep, 0.0)


def make_block_grad(cfg, full_rows: int, *, dropout: bool, matmul=f32_matmul):
    """One compiled program for every block of every step:
    ``(params, src, trg, start, key, count) -> (loss share, gradient share)``
    of the rows ``start:start+len(src)`` of a ``full_rows`` batch, the sum of
    their counted cross-entropies over ``count``."""

    def block_loss(params, s, t, start, key, count):
        drop = _SlicedDropout(
            key if dropout else None, cfg["dropout"], full_rows, start,
            s.shape[0],
        )
        total, _ = token_loss_sum(params, cfg, s, t, drop, matmul)
        return total / count

    return jax.jit(jax.value_and_grad(block_loss))


def loss_and_grads(
    params, cfg, src, trg, *, step_key=None, block_rows=None,
    matmul=f32_matmul, rows=None, block_grad=None,
):
    """Mean loss over the counted positions and its gradient, the batch
    taken ``block_rows`` rows at a time so that the full width fits.
    ``rows`` (a slice) restricts the mean to part of the batch: the planted
    faults use it. ``block_grad`` (from ``make_block_grad``) saves a compile
    where several steps follow each other."""
    n = src.shape[0]
    block_rows = block_rows or n
    lo, hi = (0, n) if rows is None else (rows.start or 0, rows.stop or n)
    count = jnp.float32(
        np.sum(np.asarray(trg[lo:hi, 1:]) != cfg["pad_id"])
    )
    if block_grad is None:
        block_grad = make_block_grad(
            cfg, n, dropout=step_key is not None, matmul=matmul
        )
    key = step_key if step_key is not None else jax.random.key(0)
    loss, grads = 0.0, None
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    for start in range(lo, hi, block_rows):
        stop = min(start + block_rows, hi)
        part, g = block_grad(
            params, src[start:stop], trg[start:stop], jnp.int32(start), key,
            count,
        )
        loss = loss + part
        grads = g if grads is None else add(grads, g)
    return loss, grads


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params), "t": 0}


@jax.jit
def _adam_apply(params, grads, m, v, t, lr, b1, b2, eps):
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1**t, 1 - b2**t
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
        params, m, v,
    )
    return params, m, v


def adam_step(params, grads, state, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Kingma & Ba's update as the recipes use it (no decay, no clipping)."""
    t = state["t"] + 1
    params, m, v = _adam_apply(
        params, grads, state["m"], state["v"], jnp.float32(t),
        jnp.float32(lr), jnp.float32(b1), jnp.float32(b2), jnp.float32(eps),
    )
    return params, {"m": m, "v": v, "t": t}


def leaf_norms(tree, block: int | None = None) -> dict[str, float]:
    """L2 norms of the tree's parts, keyed by path (``a/b/c``). A leaf
    whose last axis is a multiple of ``block`` (the model width) is taken
    in column blocks of that width, keyed ``a/b/c#j``: the fused ``qkv`` and
    ``kv`` projections then give the query, key and value parts a norm
    each, so that a rule on a part's gradient can tell the key's bias
    (whose gradient is nought under softmax) from the value's."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]

    def parts(x):
        n = x.shape[-1]
        if block and n > block and n % block == 0:
            x = x.reshape(-1, n // block, block)
            return jnp.sqrt(jnp.sum(jnp.square(x), axis=(0, 2)))
        return jnp.sqrt(jnp.sum(jnp.square(x)))[None]

    norms = jax.jit(lambda xs: [parts(x) for x in xs])([x for _, x in flat])
    out = {}
    for (path, _), values in zip(flat, norms):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        values = [float(v) for v in values]
        if len(values) == 1:
            out[name] = values[0]
        else:
            out.update({f"{name}#{j}": v for j, v in enumerate(values)})
    return out


@contextlib.contextmanager
def on_device(device):
    with jax.default_device(device), precise():
        yield
