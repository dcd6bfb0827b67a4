"""The plain reference forward of the benchmark's configurations.

A straightforward float32 decoder stack written from the configuration
file, in ``jax.numpy`` at ``"highest"`` matmul precision (a TPU otherwise
runs float32 matmuls in bf16 passes).  It imports nothing of the program:
it reads the weight tree by the names of the program's parameter
interface, whose values the benchmark made (``weights.py``).

Per layer: RMSNorm; Q, K, V projections; per-head RMSNorm of Q and K
where the configuration has ``qk_norm``; rotary embedding (rotate-half
convention, frequencies ``theta ** (-i / (head_dim / 2))``); causal grouped
attention (query head ``h`` reads K/V head ``h // (heads / kv_heads)``),
windowed where the configuration has a ``sliding_window``; output
projection; residual; RMSNorm; SwiGLU or tanh-approximated GELU MLP;
residual.  Then the final RMSNorm and the output head.

It runs one layer at a time on a block of rows, on one device: each
layer's weights are gathered from the served tree (from their shards,
on a cell of several chips) onto that device, so only one layer's
float32 copy lives there beside the served weights, and the program's
sharding never runs.  It reduces the logits over the vocabulary in chunks
to what the comparison needs.

``quant`` puts a lower precision in place of float32 for every matrix
(the embedding and the output head included), as the control of the
comparison: ``"int8"`` rounds each output channel symmetrically to 127
steps, ``"fp8"`` rounds every weight to float8_e4m3 after scaling its
output channel to the format's range.  Activations stay float32.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def on_one_device(tree):
    """``tree`` on the first device, gathered from its shards if it has
    any (an array already there is not copied)."""
    return jax.device_put(tree, jax.devices()[0])


@jax.jit
def _take_layer(stack, li):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
        stack)


def layer_on_one_device(stack, li: int):
    """Layer ``li`` of a tree of stacked layers, on the first device."""
    return on_one_device(_take_layer(stack, li))


def _quant(w: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
    """float32 copy of ``w``, rounded as ``quant`` says (per output
    channel: the last axis)."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown quant {quant!r}")


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, theta: float):
    """x: (B, S, H, hd), positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


class Reference:
    """The reference of one configuration file (a dict)."""

    def __init__(self, cfg: dict, quant: Optional[str] = None):
        self.c = cfg
        self.quant = quant
        arch = cfg["architecture"]
        if arch["norm"] != "rmsnorm" or arch["rope"] != "rotate_half":
            raise ValueError(f"reference: unsupported architecture {arch}")
        self.eps = cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))
        self.layer = jax.jit(self._layer)
        self.final = jax.jit(self._final)

    # -- one layer ---------------------------------------------------------
    def _attention(self, q, k, v):
        c = self.c
        B, S, H, hd = q.shape
        G = H // c["num_key_value_heads"]
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        win = c.get("sliding_window")
        qb = _q_block(S)
        kpos = jnp.arange(S)

        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
            qpos = i * qb + jnp.arange(qb)
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(hd)
            mask = kpos[None, :] <= qpos[:, None]
            if win:
                mask = mask & (kpos[None, :] > qpos[:, None] - win)
            s = jnp.where(mask[None, None], s, NEG)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        out = jax.lax.map(block, jnp.arange(S // qb))     # (nq, B, qb, H, hd)
        return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H * hd)

    def _layer(self, x, w):
        """x: (B, S, d) float32; ``w`` one layer's weights."""
        c, qn = self.c, self.quant
        m = w["mixer"]
        B, S, _ = x.shape
        H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
        h = rmsnorm(x, w["norm1"]["scale"], self.eps)
        q = (h @ _quant(m["wq"], qn)).reshape(B, S, H, hd)
        k = (h @ _quant(m["wk"], qn)).reshape(B, S, KV, hd)
        v = (h @ _quant(m["wv"], qn)).reshape(B, S, KV, hd)
        if c["architecture"]["qk_norm"]:
            q = rmsnorm(q, m["q_norm"], self.eps)
            k = rmsnorm(k, m["k_norm"], self.eps)
        q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
        x = x + self._attention(q, k, v) @ _quant(m["wo"], qn)
        h = rmsnorm(x, w["norm2"]["scale"], self.eps)
        f = w["ffn"]
        up = h @ _quant(f["wi_up"], qn)
        if c["architecture"]["mlp"] == "swiglu":
            g = h @ _quant(f["wi_gate"], qn)
            a = g * jax.nn.sigmoid(g) * up
        elif c["architecture"]["mlp"] == "gelu":
            a = gelu_tanh(up)
        else:
            raise ValueError(c["architecture"]["mlp"])
        return x + a @ _quant(f["wo"], qn)

    # -- head, reduced over the vocabulary ----------------------------------
    def _final(self, x, final_scale, out, pos, tgt):
        """Reduce the logits at ``pos`` (B, P) to, per position: the
        largest logit, its arg-max and the logit of token ``tgt``."""
        xs = jnp.take_along_axis(x, pos[..., None], axis=1)        # (B, P, d)
        xs = rmsnorm(xs, final_scale, self.eps)
        V = out.shape[-1]
        n = _chunks(V)
        cw = V // n
        outq = lambda i: _quant(
            jax.lax.dynamic_slice_in_dim(out, i * cw, cw, axis=1), self.quant)

        def body(carry, i):
            best, arg, at_tgt = carry
            lg = xs @ outq(i)                                      # (B, P, cw)
            cb = jnp.max(lg, -1)
            ca = jnp.argmax(lg, -1) + i * cw
            arg = jnp.where(cb > best, ca, arg)
            best = jnp.maximum(best, cb)
            local = tgt - i * cw
            hit = (local >= 0) & (local < cw)
            g = jnp.take_along_axis(lg, jnp.clip(local, 0, cw - 1)[..., None],
                                    -1)[..., 0]
            at_tgt = jnp.where(hit, g, at_tgt)
            return (best, arg, at_tgt), None

        init = (jnp.full(pos.shape, -jnp.inf), jnp.zeros(pos.shape, jnp.int32),
                jnp.zeros(pos.shape))
        (best, arg, at_tgt), _ = jax.lax.scan(body, init, jnp.arange(n))
        return best, arg, at_tgt

    # -- driver ------------------------------------------------------------
    def hidden(self, params, tokens: np.ndarray) -> jnp.ndarray:
        """float32 hidden states before the final norm, (B, S, d)."""
        with jax.default_matmul_precision("highest"):
            rows = on_one_device(
                params["embed"]["table"][jnp.asarray(tokens)])
            # an embedding row is one output channel: round it on its own
            x = _quant(rows[..., None], self.quant)[..., 0] if self.quant \
                else rows.astype(jnp.float32)
            reps = params["reps"][0]
            for li in range(self.c["num_hidden_layers"]):
                x = self.layer(x, layer_on_one_device(reps, li))
            return x

    def reduce(self, params, x, pos: np.ndarray, tgt: np.ndarray):
        with jax.default_matmul_precision("highest"):
            return self.final(x, on_one_device(params["final_norm"]["scale"]),
                              on_one_device(params["embed"]["out"]),
                              jnp.asarray(pos), jnp.asarray(tgt))


Q_BLOCK = 512      # query rows per attention block


def _q_block(S: int) -> int:
    """Largest multiple of 128 up to ``Q_BLOCK`` that divides S (else S)."""
    fits = [b for b in range(128, min(Q_BLOCK, S) + 1, 128) if S % b == 0]
    return fits[-1] if fits else S


def _chunks(V: int, target: int = 20000) -> int:
    """Smallest chunk count that divides V into chunks of <= target."""
    n = -(-V // target)
    while V % n:
        n += 1
    return n

