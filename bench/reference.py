"""The plain float32 reference of the served models, in ``jax.numpy``.

It imports nothing of the program.  It reads the weights that the
benchmark made (``model.make_weights``), laid out as the program's
parameter tree.  Each architecture's forward pass is in
``ref/<model_type>.py`` (found through ``archs.py``) and follows its
published description; it builds on the blocks kept here:

- ``_decoder``: token embedding; each layer ``x += attn(rmsnorm(x))``
  then ``x += ffn(rmsnorm(x))``, a dense SwiGLU FFN in the leading
  layers and the mixture of experts in the rest; a final RMSNorm (the
  LM head is applied by ``Sequence``, untied);
- ``_norm``: RMSNorm ``x / sqrt(mean(x^2) + eps) * w`` with the tree's
  ``scale`` holding ``w - 1``;
- ``_attention``: causal multi-head attention with grouped KV heads,
  rotate-half RoPE (``_rope``) with the configuration's ``rope_theta``,
  scores scaled by ``head_dim ** -0.5``;
- ``_swiglu``: the feed-forward ``(silu(x Wg) * x Wu) Wd``;
- ``_moe``: router logits ``x R``; ``softmax`` or ``sigmoid`` scores;
  the top-k by score; weights renormalised over the k where the
  configuration says so, times ``routed_scaling_factor``; the routed
  SwiGLU experts, weighted and summed, plus the shared experts.

Every matmul (``_mm``, ``_ein``) runs at full float32 precision.  The
control (``ctl``) is the same computation one precision lower: each
matmul operand split into a bfloat16 high part and a bfloat16 remainder,
and the product of the two remainders dropped (three bfloat16 passes,
what a TPU does for a float32 matmul at ``high``), on any backend.  A
sequence is run whole, padded at its end to a multiple of ``PAD`` tokens
so that few shapes compile; causal attention keeps the padding out of
every real position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import archs

PAD = 512           # sequences are padded to a multiple of this
ROWS = 256          # LM-head rows per block


def _bf16(a):
    # an explicit rounding to bfloat16's 8-bit exponent and 7-bit
    # mantissa, which the compiler keeps (a float32 -> bfloat16 ->
    # float32 round trip may be elided as excess precision on a TPU)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _ein(spec, a, b, ctl):
    if not ctl:
        return jnp.einsum(spec, a, b)
    ah, al = _split(a)
    bh, bl = _split(b)
    return (jnp.einsum(spec, ah, bh) + jnp.einsum(spec, ah, bl)
            + jnp.einsum(spec, al, bh))


def _mm(a, b, ctl):
    return _ein("...i,ij->...j", a, b, ctl)


def _norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (S, H, D); positions 0..S-1."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, c, ctl):
    S = x.shape[0]
    H, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    q = _rope(_mm(x, p["wq"], ctl).reshape(S, H, D), c["rope_theta"])
    k = _rope(_mm(x, p["wk"], ctl).reshape(S, Hkv, D), c["rope_theta"])
    v = _mm(x, p["wv"], ctl).reshape(S, Hkv, D)
    kr = jnp.repeat(k, H // Hkv, axis=1)
    vr = jnp.repeat(v, H // Hkv, axis=1)
    s = _ein("qhd,khd->hqk", q, kr, ctl) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = _ein("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vr, ctl)
    return _mm(o.reshape(S, H * D), p["wo"], ctl), (k, v)


def _swiglu(x, wg, wu, wd, ctl):
    return _mm(jax.nn.silu(_mm(x, wg, ctl)) * _mm(x, wu, ctl), wd, ctl)


def _moe(p, x, c, ctl):
    logits = _mm(x, p["router"], ctl)
    if c.get("scoring_func", "softmax") == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    k = c["num_experts_per_tok"]
    top, idx = jax.lax.top_k(scores, k + 1)
    margin = top[:, k - 1] - top[:, k]      # how near the choice is a tie
    top, idx = top[:, :k], idx[:, :k]
    if c.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * c.get("routed_scaling_factor", 1.0)
    E = p["w_gate"].shape[0]
    # (S, E) combine weights: zero where the expert was not chosen
    comb = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], axis=1)

    def expert(y, xs):
        wg, wu, wd, w = xs
        return y + w[:, None] * _swiglu(x, wg, wu, wd, ctl), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], comb.T))
    if "shared" in p:
        sh = p["shared"]
        y = y + _swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"], ctl)
    return y, margin


def _layer(p, x, c, kind, ctl):
    eps = c["rms_norm_eps"]
    a, rows = _attention(p["attn"], _norm(x, p["norm1"]["scale"], eps), c,
                         ctl)
    x = x + a
    h = _norm(x, p["norm2"]["scale"], eps)
    if kind == "moe":
        y, margin = _moe(p["moe"], h, c, ctl)
        return x + y, rows, margin
    f = p["ffn"]
    return (x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], ctl), rows,
            jnp.full(x.shape[:1], jnp.inf))


def _decoder(params, tokens, c, ctl):
    """The program's decoder tree with grouped-KV attention
    (``_attention``) and the mixture of experts (``_moe``), the forward
    pass of an architecture file whose blocks are these: final hidden
    states, each layer's cache rows (K after RoPE, V), and per position
    the smallest router margin over the MoE layers (the gap between the
    k-th and the (k+1)-th expert's score)."""
    x = params["embed"][tokens]
    caches = []
    margin = jnp.full(tokens.shape, jnp.inf)
    for p in params.get("prefix", []):
        x, rows, m = _layer(p, x, c, "dense", ctl)
        caches.append(rows)
    body = params["body"]
    n = jax.tree.leaves(body)[0].shape[0]
    for g in range(n):
        x, rows, m = _layer(jax.tree.map(lambda a: a[g], body["b0"]), x, c,
                            "moe", ctl)
        caches.append(rows)
        margin = jnp.minimum(margin, m)
    return (_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"]),
            caches, margin)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _forward(params, tokens, model_type, cfg_items, ctl):
    return archs.reference(model_type).forward(params, tokens,
                                               dict(cfg_items), ctl)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_rows(h, head, tokens, ctl):
    """Per row: the largest logit, its token, and the logit of
    ``tokens`` — over blocks of ``ROWS`` rows."""
    S, d = h.shape

    def block(args):
        hb, tb = args
        logits = _mm(hb, head, ctl)
        return (jnp.max(logits, -1),
                jnp.argmax(logits, -1).astype(jnp.int32),
                jnp.take_along_axis(logits, tb[:, None], -1)[:, 0])
    out = jax.lax.map(block, (h.reshape(S // ROWS, ROWS, d),
                              tokens.reshape(S // ROWS, ROWS)))
    return tuple(o.reshape(S) for o in out)


def _items(c: dict, keys) -> tuple:
    """The configuration's ``keys`` as a static argument of ``jit``,
    each as run where the file states the program's departure
    (``as_run``)."""
    as_run = c.get("as_run", {})
    return tuple((k, as_run.get(k, c[k])) for k in keys)


class Sequence:
    """The reference over one request: ``prompt`` + ``served[:-1]``,
    full precision (and the control beside it with ``control=True``)."""

    def __init__(self, c: dict, params, prompt, served, *, control=False):
        prompt = np.asarray(prompt, np.int32)
        self.served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, self.served[:-1]])
        S = -(-len(seq) // PAD) * PAD
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        self.first = len(prompt) - 1
        self.params, self.S = params, S
        t = c["model_type"]
        items = _items(c, archs.reference(t).KEYS)
        with jax.default_matmul_precision("highest"):
            self.h, self.kv, margin = _forward(
                params, jnp.asarray(toks), t, items, False)
            if control:
                self.h_ctl, self.kv_ctl, _ = _forward(
                    params, jnp.asarray(toks), t, items, True)
        self.margin = np.asarray(margin)

    def _rows(self, h, read, ctl):
        at = np.zeros(self.S, np.int32)
        sl = slice(self.first, self.first + len(self.served))
        at[sl] = read
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(_head_rows(h, self.params["head"],
                                            jnp.asarray(at), ctl))
        return tuple(o[sl] for o in out)

    def near_tie(self, tie: float) -> np.ndarray:
        """Per position: whether some MoE layer chose its experts by less
        than ``tie`` of score — where rounding alone may route it
        otherwise."""
        return self.margin < tie

    def served_gaps(self, read=None) -> np.ndarray:
        """How far each served token's (or ``read``'s) logit lies below
        the best."""
        best, _, val = self._rows(self.h, self.served if read is None
                                  else read, False)
        return best - val

    def control_gaps(self) -> np.ndarray:
        """The same gaps for the tokens the control puts first."""
        _, arg, _ = self._rows(self.h_ctl, self.served, True)
        best, _, val = self._rows(self.h, arg, False)
        return best - val

    def served_near_tie(self, tie: float) -> np.ndarray:
        return self.near_tie(tie)[self.first:self.first + len(self.served)]

    def prompt_kv(self, n: int, control=False) -> list:
        """Each layer's cache rows (for grouped-KV attention, K and V)
        at positions [0, n), as numpy arrays."""
        kv = self.kv_ctl if control else self.kv
        return jax.device_get([tuple(a[:n] for a in layer)
                               for layer in kv])
