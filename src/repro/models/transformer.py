"""Model assembly: every assigned architecture as one composable stack.

The layer sequence is factored into ``R`` repetitions of the arch's block
pattern (``('attn',)`` for dense, ``('ssm',)`` for mamba, ``('rglru',
'rglru', 'attn')`` for recurrentgemma, ...) plus an unrolled remainder.
Repetitions run under one ``jax.lax.scan`` with parameters stacked on a
leading ``R`` axis, so the lowered HLO (and compile time) is O(1) in depth —
mandatory for the 96-layer dry-run configs.

Three entry points, one per program phase (the per-phase granularity at
which the AMOEBA controller reconfigures the mesh):

* :func:`loss_fn`       — full-sequence teacher-forced LM loss (train_4k)
* :func:`prefill`       — full-sequence forward that builds decode state
                          (prefill_32k)
* :func:`decode_step`   — one new token against the cached state
                          (decode_32k / long_500k)

The LM loss streams over sequence chunks (``lax.scan`` + ``jax.checkpoint``)
so the fp32 (B, S, V) logits tensor is never materialized — for the
256k-vocab configs that is the difference between fitting HBM and not.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention, layers, moe, rglru, ssm
from repro.models.attention import KVCache
from repro.models.moe import MoEAux
from repro.models.rglru import RGLRUState
from repro.models.ssm import SSMState
from repro.parallel import shardctx


# ---------------------------------------------------------------------------
# Runtime options (static over a jit)
# ---------------------------------------------------------------------------

class Runtime(NamedTuple):
    """Static execution knobs threaded through the stack."""
    use_kernels: bool = False     # Pallas kernels (TPU) vs pure-jnp oracles
    production: bool = True       # sharded MoE vs dense oracle
    remat: bool = True            # per-block activation checkpointing
    q_block: int = 512            # attention q/kv block sizes
    kv_block: int = 1024
    loss_chunk: int = 512         # vocab-loss sequence chunk
    # Megatron-SP: residual stream sharded over 'model' on the sequence dim
    # between blocks — saved remat residuals shrink by the TP width (the
    # difference between 340B fitting v5e HBM and not).
    seq_shard: bool = False
    # int8 KV cache (+ per-vector scales): ~2x less decode HBM traffic
    # (beyond-paper optimization, EXPERIMENTS.md §Perf C2)
    kv_quant: bool = False


DEFAULT_RT = Runtime()


def _pattern(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.block_pattern is not None:
        return tuple(cfg.block_pattern)
    return ("ssm",) if cfg.family == "ssm" else ("attn",)


def _has_ffn(cfg: ModelConfig, kind: str) -> bool:
    return kind != "ssm" and (cfg.moe is not None or cfg.d_ff > 0)


def _zero_aux(cfg: ModelConfig) -> MoEAux:
    e = cfg.moe.num_experts if cfg.moe is not None else 1
    return MoEAux(aux_loss=jnp.zeros(()), load=jnp.zeros((e,)),
                  dropped=jnp.zeros(()))


def _add_aux(a: MoEAux, b: MoEAux) -> MoEAux:
    return MoEAux(aux_loss=a.aux_loss + b.aux_loss,
                  load=a.load + b.load, dropped=a.dropped + b.dropped)


# ---------------------------------------------------------------------------
# One block: norm -> mixer -> (cross-attn) -> norm -> ffn, pre-norm residual
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, cross: bool = False):
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    params: Dict[str, Any] = {}
    pspecs: Dict[str, Any] = {}
    params["norm1"], pspecs["norm1"] = layers.init_rmsnorm(cfg.d_model, dtype)
    if kind == "attn":
        params["mixer"], pspecs["mixer"] = attention.init_attention(ks[0], cfg)
    elif kind == "ssm":
        params["mixer"], pspecs["mixer"] = ssm.init_ssm(ks[0], cfg)
    elif kind == "rglru":
        params["mixer"], pspecs["mixer"] = rglru.init_rglru(ks[0], cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if cross and kind == "attn":
        params["cross_norm"], pspecs["cross_norm"] = \
            layers.init_rmsnorm(cfg.d_model, dtype)
        params["cross_attn"], pspecs["cross_attn"] = \
            attention.init_attention(ks[1], cfg, cross=True)
    if _has_ffn(cfg, kind):
        params["norm2"], pspecs["norm2"] = layers.init_rmsnorm(cfg.d_model, dtype)
        if cfg.moe is not None:
            params["ffn"], pspecs["ffn"] = moe.init_moe(ks[2], cfg)
        else:
            params["ffn"], pspecs["ffn"] = layers.init_mlp(
                ks[2], cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    return params, pspecs


def _pin_block_params(params: Dict[str, Any], kind: str,
                      cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """Re-assert the FSDP sharding of the big per-layer weights.

    Inside a scan-over-layers XLA is free to hoist the 'data'-axis
    all-gather of the whole stacked weight out of the loop — materializing
    an unsharded copy of every layer at once (tens of GB at 340B scale).
    Pinning each slice to its stored sharding keeps the gather inside the
    (rematted) block, so only one layer's weights are ever live.
    """
    kv_spec = ("data", "model") if (cfg is not None
                                    and cfg.num_kv_heads % 4 == 0) \
        else ("data", None)
    pins = {"wq": ("data", "model"), "wk": kv_spec,
            "wv": kv_spec, "wo": ("model", "data"),
            "wi_gate": ("data", "model"), "wi_up": ("data", "model"),
            "in_proj": ("data", "model"), "out_proj": ("model", "data"),
            "in_x": ("data", "model"), "in_gate": ("data", "model"),
            "wa": ("data", "model"), "wx": ("data", "model"),
            "out": ("model", "data")}

    def pin(tree):
        out = dict(tree)
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = pin(v)
            elif k in pins and hasattr(v, "ndim") and v.ndim == 2:
                out[k] = shardctx.hint(v, *pins[k])
            elif k in ("wi_gate", "wi_up", "wo") and hasattr(v, "ndim") \
                    and v.ndim == 3:   # expert banks (E, D, F)
                spec = ("model", "data", None) if k != "wo" \
                    else ("model", None, "data")
                out[k] = shardctx.hint(v, *spec)
        return out

    return pin(params)


def block_forward(params, x, positions, encoder_out, cfg: ModelConfig,
                  kind: str, rt: Runtime, *, causal: bool = True,
                  build_cache: bool = False, cache_window: Optional[int] = None):
    """Full-sequence block. Returns (x, aux, cache_or_None)."""
    if rt.production and shardctx.current_mesh() is not None:
        params = _pin_block_params(params, kind, cfg)

    def gather_seq(h):
        # Megatron-SP transition: residual/norms live S-sharded over
        # 'model'; compute regions run on the gathered sequence (otherwise
        # the partitioner replicates the weights instead — fatal at 340B).
        # Double constraint asks the partitioner to materialize the bf16
        # norm output S-sharded before gathering (so the SP all-gather
        # moves bf16, not the fp32 intermediate).  §Perf iteration A1:
        # XLA-CPU's partitioner ignores the ordering and gathers fp32
        # anyway (hypothesis refuted there); kept because the constraint is
        # free and the TPU partitioner honors operand-dtype boundaries.
        if rt.seq_shard:
            h = shardctx.hint(h, "batch", "model", None)
            return shardctx.hint(h, "batch", None, None)
        return h

    def scatter_seq(y):
        # inverse transition: sublayer outputs return to the S-sharded
        # residual stream.  Intended to lower the TP combine as a
        # reduce-scatter; XLA-CPU still emits all-reduce + slice (§Perf A1,
        # refuted on this backend), but the constraint is what the TPU
        # partitioner needs to pick reduce-scatter.
        if rt.seq_shard:
            return shardctx.hint(y, "batch", "model", None)
        return y

    h = gather_seq(layers.rmsnorm(params["norm1"], x, cfg.norm_eps))
    cache = None
    if kind == "attn":
        mix = attention.full_attention(
            params["mixer"], h, positions, cfg, causal=causal,
            use_flash=rt.use_kernels, q_block=rt.q_block, kv_block=rt.kv_block)
        if build_cache:
            cache = {"self": attention.prefill_cache(
                params["mixer"], h, positions, cfg,
                window_override=cache_window, quant=rt.kv_quant)}
    elif kind == "ssm":
        out = ssm.ssm_forward(params["mixer"], h, cfg,
                              use_kernel=rt.use_kernels,
                              return_state=build_cache)
        if build_cache:
            mix, st = out
            cache = {"self": st}
        else:
            mix = out
    else:  # rglru
        out = rglru.rglru_forward(params["mixer"], h, cfg,
                                  use_kernel=rt.use_kernels,
                                  return_state=build_cache)
        if build_cache:
            mix, st = out
            cache = {"self": st}
        else:
            mix = out
    x = x + scatter_seq(mix)
    if "cross_attn" in params and encoder_out is not None:
        h = gather_seq(layers.rmsnorm(params["cross_norm"], x, cfg.norm_eps))
        x = x + attention.full_attention(
            params["cross_attn"], h, None, cfg, causal=False,
            encoder_out=encoder_out, q_block=rt.q_block, kv_block=rt.kv_block)
        if build_cache:
            cache["cross"] = attention.build_cross_cache(
                params["cross_attn"], encoder_out, cfg)
    aux = _zero_aux(cfg)
    if "ffn" in params:
        h = gather_seq(layers.rmsnorm(params["norm2"], x, cfg.norm_eps))
        if cfg.moe is not None:
            y, aux = moe.moe_forward(params["ffn"], h, cfg,
                                     production=rt.production)
        else:
            y = layers.mlp(params["ffn"], h, cfg.activation)
        x = x + scatter_seq(y)
    x = shardctx.hint(x, "batch", "model" if rt.seq_shard else None, None)
    return x, aux, cache


def block_decode(params, state, x_new, pos, cfg: ModelConfig, kind: str,
                 rt: Runtime, rope_pos=None, layer=None):
    """One-token block step. x_new: (B,1,D). Returns (x, new_state).

    With ``layer`` the state's leaves are stacked over layers (a leading
    axis) and the step reads and updates that layer; the self-attention
    cache is written in place in the stack (see ``decode_attention``).
    """
    def at_layer(tree):
        return tree if layer is None else jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), tree)

    def put_layer(stack, tree):
        return tree if layer is None else jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
            stack, tree)

    h = layers.rmsnorm(params["norm1"], x_new, cfg.norm_eps)
    new_state = dict(state)
    if kind == "attn":
        mix, new_state["self"] = attention.decode_attention(
            params["mixer"], state["self"], h, pos, cfg, rope_pos=rope_pos,
            layer=layer)
    elif kind == "ssm":
        mix, ns = ssm.ssm_step(params["mixer"], at_layer(state["self"]), h,
                               cfg)
        new_state["self"] = put_layer(state["self"], ns)
    else:
        mix, ns = rglru.rglru_step(params["mixer"], at_layer(state["self"]),
                                   h, cfg)
        new_state["self"] = put_layer(state["self"], ns)
    x = x_new + mix
    if "cross" in state:
        cross = at_layer(state["cross"])
        h = layers.rmsnorm(params["cross_norm"], x, cfg.norm_eps)
        enc_len = cross.k.shape[1]
        enc_pos = jnp.full((x.shape[0],), enc_len, jnp.int32)
        out, _ = attention.decode_attention(
            params["cross_attn"], cross, h, enc_pos, cfg,
            update=False, cross=True)
        x = x + out
    if "ffn" in params:
        h = layers.rmsnorm(params["norm2"], x, cfg.norm_eps)
        if cfg.moe is not None:
            y, _ = moe.moe_forward(params["ffn"], h, cfg,
                                   production=rt.production)
        else:
            y = layers.mlp(params["ffn"], h, cfg.activation)
        x = x + y
    return x, new_state


# ---------------------------------------------------------------------------
# Whole-model parameters
# ---------------------------------------------------------------------------

def _stack_blocks(pairs):
    """[(params, pspecs)] with identical structure -> (stacked, pspecs+lead)."""
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *[p for p, _ in pairs])
    is_p = lambda x: isinstance(x, P)
    pspecs = jax.tree.map(lambda s: P(*((None,) + tuple(s))),
                          pairs[0][1], is_leaf=is_p)
    return params, pspecs


def init_model(key, cfg: ModelConfig):
    """Returns (params, pspecs). Run under jax.eval_shape for the dry-run."""
    pattern = _pattern(cfg)
    L, PL = cfg.num_layers, len(pattern)
    R, rem = divmod(L, PL)
    keys = jax.random.split(key, 3 + L + cfg.encoder_layers)
    dtype = jnp.dtype(cfg.dtype)

    params: Dict[str, Any] = {}
    pspecs: Dict[str, Any] = {}
    params["embed"], pspecs["embed"] = layers.init_embedding(
        keys[0], cfg.vocab_size, cfg.d_model, dtype, cfg.tie_embeddings)
    params["final_norm"], pspecs["final_norm"] = \
        layers.init_rmsnorm(cfg.d_model, dtype)

    cross = cfg.cross_attention
    kidx = 3
    if R > 0:
        reps_p, reps_s = [], []
        for i, kind in enumerate(pattern):
            pairs = []
            for r in range(R):
                pairs.append(init_block(keys[kidx + r * PL + i], cfg, kind,
                                        cross=cross))
            sp, ss = _stack_blocks(pairs)
            reps_p.append(sp)
            reps_s.append(ss)
        params["reps"] = tuple(reps_p)
        pspecs["reps"] = tuple(reps_s)
    kidx += R * PL
    if rem:
        rest_p, rest_s = [], []
        for j in range(rem):
            p, s = init_block(keys[kidx + j], cfg, pattern[j % PL], cross=cross)
            rest_p.append(p)
            rest_s.append(s)
        params["rest"] = tuple(rest_p)
        pspecs["rest"] = tuple(rest_s)

    if cfg.encoder_layers:
        pairs = [init_block(keys[3 + L + e], cfg, "attn", cross=False)
                 for e in range(cfg.encoder_layers)]
        params["encoder"], pspecs["encoder"] = _stack_blocks(pairs)
        params["enc_norm"], pspecs["enc_norm"] = \
            layers.init_rmsnorm(cfg.d_model, dtype)
    return params, pspecs


def model_pspecs(cfg: ModelConfig):
    """Parameter PartitionSpec tree without allocating any parameters."""
    holder = {}

    def f(key):
        p, s = init_model(key, cfg)
        holder["pspecs"] = s     # static python objects captured at trace time
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, holder["pspecs"]


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Input embedding / positions per family
# ---------------------------------------------------------------------------

def _mrope_positions(B: int, S: int, n_vision: int) -> jnp.ndarray:
    """(B, 3, S) (temporal, h, w) M-RoPE indices: a vision-patch grid prefix
    followed by text positions (all three components advance together)."""
    idx = jnp.arange(S)
    side = max(1, int(math.ceil(math.sqrt(max(n_vision, 1)))))
    is_vis = idx < n_vision
    t = jnp.where(is_vis, 0, idx - n_vision + side)
    h = jnp.where(is_vis, idx // side, idx - n_vision + side)
    w = jnp.where(is_vis, idx % side, idx - n_vision + side)
    pos = jnp.stack([t, h, w], axis=0)                       # (3, S)
    return jnp.broadcast_to(pos[None], (B, 3, S)).astype(jnp.int32)


def embed_inputs(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig):
    """-> (x (B,S,D), positions, encoder_out_or_None)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = layers.embed(params["embed"], tokens)
    encoder_out = None
    if cfg.encoder_layers:
        # whisper: conv frontend is a stub — precomputed frame embeddings.
        enc = batch["audio_embeds"]
        enc = enc + layers.sinusoidal_positions(
            enc.shape[1], cfg.d_model).astype(enc.dtype)
        encoder_out = encode(params, enc, cfg)
        x = x + layers.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
        positions = None                      # sinusoidal, no RoPE
    elif cfg.vision_stub and "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(x.dtype)         # (B, V, D)
        V = vis.shape[1]
        x = jnp.concatenate([vis, x[:, V:]], axis=1)
        positions = _mrope_positions(B, S, V)
    elif cfg.mrope:
        positions = _mrope_positions(B, S, 0)
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    x = shardctx.hint(x, "batch", None, None)
    return x, positions, encoder_out


def encode(params, enc_in: jnp.ndarray, cfg: ModelConfig,
           rt: Runtime = DEFAULT_RT) -> jnp.ndarray:
    """Whisper encoder: bidirectional attention over frame embeddings."""
    def body(x, blk_params):
        def one(p, x):
            y, _, _ = block_forward(p, x, None, None, cfg, "attn", rt,
                                    causal=False)
            return y
        f = jax.checkpoint(one) if rt.remat else one
        return f(blk_params, x), None

    x, _ = jax.lax.scan(body, enc_in, params["encoder"])
    return layers.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Full-sequence forward (shared by loss / logits / prefill)
# ---------------------------------------------------------------------------

def forward_hidden(params, x, positions, encoder_out, cfg: ModelConfig,
                   rt: Runtime, build_cache: bool = False,
                   cache_window: Optional[int] = None):
    """Runs the decoder stack. Returns (hidden, aux, caches)."""
    pattern = _pattern(cfg)
    PL = len(pattern)
    aux = _zero_aux(cfg)
    caches_rep, caches_rest = None, None

    def one_block(p, x, positions, encoder_out, kind):
        return block_forward(p, x, positions, encoder_out, cfg, kind, rt,
                             causal=True, build_cache=build_cache,
                             cache_window=cache_window)

    if "reps" in params:
        def rep_body(carry, rep_params):
            x, aux = carry
            caches = []
            for i, kind in enumerate(pattern):
                f = partial(one_block, kind=kind)
                if rt.remat and not build_cache:
                    f = jax.checkpoint(f)
                x, a, c = f(rep_params[i], x, positions, encoder_out)
                aux = _add_aux(aux, a)
                caches.append(c)
            ys = tuple(caches) if build_cache else None
            return (x, aux), ys

        (x, aux), caches_rep = jax.lax.scan(rep_body, (x, aux), params["reps"])

    if "rest" in params:
        caches = []
        for j, p in enumerate(params["rest"]):
            kind = pattern[j % PL]
            f = partial(one_block, kind=kind)
            if rt.remat and not build_cache:
                f = jax.checkpoint(f)
            x, a, c = f(p, x, positions, encoder_out)
            aux = _add_aux(aux, a)
            caches.append(c)
        caches_rest = tuple(caches) if build_cache else None

    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, (caches_rep, caches_rest)


def logits_fn(params, batch, cfg: ModelConfig, rt: Runtime = DEFAULT_RT):
    """Full (B,S,V) logits — smoke-test scale only."""
    x, positions, enc = embed_inputs(params, batch, cfg)
    x, aux, _ = forward_hidden(params, x, positions, enc, cfg, rt)
    return layers.unembed(params["embed"], x, cfg.tie_embeddings), aux


def reference_logits(params, tokens: jnp.ndarray, cfg: ModelConfig):
    """Float32 full-sequence logits, one layer on the device at a time.

    The plain reference for a served model: the same equations as
    :func:`logits_fn`, on float32 copies of the weights, with float32
    matmuls at ``"highest"`` precision (a TPU otherwise runs float32
    matmuls in bf16 passes).  Only one layer's float32 copy exists at a
    time, so a model whose float32 weights exceed device memory still
    fits beside its bf16 weights.  Decoder-only stacks; tokens (B, S).
    """
    if cfg.encoder_layers or cfg.vision_stub:
        raise NotImplementedError("reference_logits: decoder-only stacks")
    cfg32 = cfg.replace(dtype="float32")
    rt = Runtime(production=False, remat=False)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    pattern = _pattern(cfg)

    @partial(jax.jit, static_argnames="kind")
    def block(p, x, positions, kind):
        return block_forward(f32(p), x, positions, None, cfg32, kind, rt)[0]

    @jax.jit
    def head(p, x):
        x = layers.rmsnorm(f32(p["final_norm"]), x, cfg.norm_eps)
        return layers.unembed(f32(p["embed"]), x, cfg.tie_embeddings)

    with jax.default_matmul_precision("highest"):
        x, positions, _ = embed_inputs(params, {"tokens": tokens}, cfg)
        x = x.astype(jnp.float32)
        n_rep = cfg.num_layers // len(pattern) if "reps" in params else 0
        for r in range(n_rep):
            for i, kind in enumerate(pattern):
                p = jax.tree.map(lambda a: a[r], params["reps"][i])
                x = block(p, x, positions, kind=kind)
        for j, p in enumerate(params.get("rest", ())):
            x = block(p, x, positions, kind=pattern[j % len(pattern)])
        return head({"final_norm": params["final_norm"],
                     "embed": params["embed"]}, x)


# ---------------------------------------------------------------------------
# Training loss (chunked over sequence, vocab sharded over 'model')
# ---------------------------------------------------------------------------

def _chunked_lm_loss(params, x, tokens, cfg: ModelConfig, chunk: int):
    """Mean NLL of tokens[:,1:] given hidden x[:,:-1]; O(chunk·V) memory."""
    B, S, D = x.shape
    n = S - 1
    xs, tg = x[:, :-1], tokens[:, 1:]
    c = min(chunk, n)
    nc = -(-n // c)
    pad = nc * c - n
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        tg = jnp.pad(tg, ((0, 0), (0, pad)))
    valid = (jnp.arange(nc * c) < n).astype(jnp.float32)     # (nc*c,)
    xs = xs.reshape(B, nc, c, D).transpose(1, 0, 2, 3)
    tg = tg.reshape(B, nc, c).transpose(1, 0, 2)
    vd = valid.reshape(nc, c)

    def chunk_nll(xc, tc, vc):
        logits = layers.unembed(params["embed"], xc, cfg.tie_embeddings)
        logits = shardctx.hint(logits, "batch", None, "model")
        lg = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)       # (B, c)
        picked = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - picked) * vc[None, :])

    body_fn = jax.checkpoint(chunk_nll)

    def body(acc, inp):
        xc, tc, vc = inp
        return acc + body_fn(xc, tc, vc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, tg, vd))
    return total / (B * n)


def loss_fn(params, batch, cfg: ModelConfig, rt: Runtime = DEFAULT_RT):
    """-> (loss, metrics). metrics carries the AMOEBA divergence signals."""
    x, positions, enc = embed_inputs(params, batch, cfg)
    x, aux, _ = forward_hidden(params, x, positions, enc, cfg, rt)
    lm = _chunked_lm_loss(params, x, batch["tokens"], cfg, rt.loss_chunk)
    loss = lm
    n_moe = sum(1 for k in cfg.layer_kinds if k != "ssm") or 1
    metrics = {"lm_loss": lm}
    if cfg.moe is not None:
        aux_mean = aux.aux_loss / n_moe
        loss = loss + cfg.moe.router_aux_loss * aux_mean
        metrics.update(moe_aux=aux_mean, expert_load=aux.load / n_moe,
                       dropped_frac=aux.dropped / n_moe)
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode state: prefill + one-token step
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    pos: jnp.ndarray                       # (B,) next absolute position
    rope_offset: jnp.ndarray               # (B,) rope_pos = pos + offset (M-RoPE)
    reps: Any                              # tuple per pattern position, stacked (R, ...)
    rest: Any                              # tuple per remainder layer


def init_decode_state(cfg: ModelConfig, batch: int, seq_len: int,
                      enc_len: int = 0, kv_quant: bool = False) -> DecodeState:
    """Zero-initialized state sized for a seq_len-token context window."""
    pattern = _pattern(cfg)
    L, PL = cfg.num_layers, len(pattern)
    R, rem = divmod(L, PL)

    def one(kind):
        if kind == "attn":
            st = {"self": attention.init_cache(cfg, batch, seq_len,
                                               quant=kv_quant)}
            if cfg.cross_attention:
                hd = cfg.resolved_head_dim
                z = jnp.zeros((batch, enc_len, cfg.num_kv_heads, hd),
                              jnp.dtype(cfg.dtype))
                st["cross"] = KVCache(k=z, v=z)
            return st
        if kind == "ssm":
            return {"self": ssm.init_ssm_state(cfg, batch)}
        return {"self": rglru.init_rglru_state(cfg, batch)}

    def stack(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                            tree)

    reps = tuple(stack(one(k), R) for k in pattern) if R else ()
    rest = tuple(one(pattern[j % PL]) for j in range(rem))
    return DecodeState(pos=jnp.zeros((batch,), jnp.int32),
                       rope_offset=jnp.zeros((batch,), jnp.int32),
                       reps=reps, rest=rest)


def decode_state_pspecs(cfg: ModelConfig, kv_quant: bool = False):
    """PartitionSpec tree matching init_decode_state (leading scan dim on reps).

    Uses the 'batch' placeholder resolved by repro.parallel.resolve.
    """
    pattern = _pattern(cfg)
    L, PL = cfg.num_layers, len(pattern)
    R, rem = divmod(L, PL)

    def one(kind):
        if kind == "attn":
            st = {"self": attention.cache_pspec(quant=kv_quant)}
            if cfg.cross_attention:
                st["cross"] = KVCache(k=P("batch", None, None, None),
                                      v=P("batch", None, None, None))
            return st
        if kind == "ssm":
            return {"self": ssm.ssm_state_pspec()}
        return {"self": rglru.rglru_state_pspec()}

    is_p = lambda x: isinstance(x, P)
    lead = lambda t: jax.tree.map(lambda s: P(*((None,) + tuple(s))), t,
                                  is_leaf=is_p)
    reps = tuple(lead(one(k)) for k in pattern) if R else ()
    rest = tuple(one(pattern[j % PL]) for j in range(rem))
    return DecodeState(pos=P("batch"), rope_offset=P("batch"),
                       reps=reps, rest=rest)


def prefill(params, batch, cfg: ModelConfig, rt: Runtime = DEFAULT_RT,
            window: Optional[int] = None):
    """Full-sequence forward that also builds the decode state.

    Returns (last_logits (B, V), DecodeState).  ``window`` sets the decode
    horizon (cache length); defaults to the prompt length — pass the full
    generation horizon when decoding past the prompt with dense attention.
    """
    x, positions, enc = embed_inputs(params, batch, cfg)
    x, _, (caches_rep, caches_rest) = forward_hidden(
        params, x, positions, enc, cfg, rt, build_cache=True,
        cache_window=window)
    last = x[:, -1]
    logits = layers.unembed(params["embed"], last[:, None],
                            cfg.tie_embeddings)[:, 0]
    B, S = batch["tokens"].shape
    pos = jnp.full((B,), S, jnp.int32)
    # M-RoPE: text positions run (i - V + side); carry the offset for decode
    offset = jnp.zeros((B,), jnp.int32)
    if cfg.vision_stub and "vision_embeds" in batch:
        V = batch["vision_embeds"].shape[1]
        side = max(1, int(math.ceil(math.sqrt(max(V, 1)))))
        offset = jnp.full((B,), side - V, jnp.int32)
    return logits, DecodeState(pos=pos, rope_offset=offset,
                               reps=caches_rep or (),
                               rest=caches_rest or ())


def decode_step(params, state: DecodeState, new_tokens: jnp.ndarray,
                cfg: ModelConfig, rt: Runtime = DEFAULT_RT):
    """new_tokens: (B, 1) int32 -> (logits (B, V), new DecodeState)."""
    pattern = _pattern(cfg)
    PL = len(pattern)
    pos = state.pos
    rope_pos = pos + state.rope_offset
    x = layers.embed(params["embed"], new_tokens)            # (B,1,D)
    if cfg.encoder_layers:
        # sinusoidal position of the new token
        d = cfg.d_model
        half = d // 2
        freq = jnp.exp(-math.log(10_000.0)
                       * jnp.arange(half, dtype=jnp.float32) / (half - 1))
        ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(x.dtype)
        x = x + pe[:, None, :]
    x = shardctx.hint(x, "batch", None, None)

    new_reps = ()
    if state.reps:
        # the stacked layer states ride in the carry and each layer's step
        # updates its slot of the stack, so a donated state is updated in
        # place (as the scan's inputs and outputs, XLA keeps a second copy
        # of every cache, and a layer sliced out and written back is
        # copied twice)
        def rep_body(carry, rep_params):
            x, reps, i = carry
            new_states = []
            for j, kind in enumerate(pattern):
                x, ns = block_decode(rep_params[j], reps[j], x, pos, cfg,
                                     kind, rt, rope_pos=rope_pos, layer=i)
                new_states.append(ns)
            return (x, tuple(new_states), i + 1), None

        (x, new_reps, _), _ = jax.lax.scan(
            rep_body, (x, state.reps, jnp.int32(0)), params["reps"])

    new_rest = []
    for j, p in enumerate(params.get("rest", ())):
        x, ns = block_decode(p, state.rest[j], x, pos, cfg, pattern[j % PL],
                             rt, rope_pos=rope_pos)
        new_rest.append(ns)

    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = layers.unembed(params["embed"], x, cfg.tie_embeddings)[:, 0]
    return logits, DecodeState(pos=pos + 1, rope_offset=state.rope_offset,
                               reps=new_reps, rest=tuple(new_rest))
