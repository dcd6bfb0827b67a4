"""Mixture-of-Experts FFN: fine-grained routed experts (+ shared experts,
+ optional arctic-style dense residual branch).

Two execution paths:

* ``moe_dense`` — capacity-free oracle: every expert runs on every token and
  results are combined by routing weight.  O(E·T·D·F): used for smoke-scale
  configs and as the ground truth in tests.

* ``moe_sharded`` — the production path.  Experts are sharded over the
  'model' axis (EP) and tokens over the batch axes; since tokens are
  *replicated* across 'model', each (data, model) device selects the subset
  of its local tokens routed to its local experts, packs them into a
  per-expert capacity buffer (scatter by intra-expert cumsum), runs the
  expert FFN as one static einsum, scatters back, and a single ``psum`` over
  'model' both combines expert contributions and restores replication.
  No all-to-all is needed in this layout — the AMOEBA analogy: a fused
  group shares one coalesced "memory port" instead of exchanging packets.

  Expert weights are additionally sharded over 'data' on D (FSDP) and
  all-gathered per layer inside the shard_map region; the transpose of that
  gather is the reduce-scatter that keeps gradient memory flat.

Returns routing telemetry (expert load fractions, dropped-token fraction)
— the **divergence signal** consumed by the AMOEBA controller.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.parallel import shardctx


class MoEAux(NamedTuple):
    aux_loss: jnp.ndarray       # scalar load-balance loss
    load: jnp.ndarray           # (E,) fraction of assignments per expert
    dropped: jnp.ndarray        # scalar fraction of dropped assignments


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    std_in = 1.0 / math.sqrt(d)
    std_out = 1.0 / math.sqrt(f)
    gated = cfg.activation == "swiglu"

    def expert_bank(key, n):
        kk = jax.random.split(key, 3)
        bank = {
            "wi_up": layers.truncated_normal(kk[0], (n, d, f), std_in, dtype),
            "wo": layers.truncated_normal(kk[1], (n, f, d), std_out, dtype),
        }
        if gated:
            bank["wi_gate"] = layers.truncated_normal(kk[2], (n, d, f), std_in, dtype)
        return bank

    params = {
        "router": layers.truncated_normal(ks[0], (d, m.num_experts), std_in,
                                          jnp.float32),
        "experts": expert_bank(ks[1], m.num_experts),
    }
    pspecs = {
        "router": P(None, None),
        "experts": {k: P("model", "data", None) if k != "wo"
                    else P("model", None, "data")
                    for k in params["experts"]},
    }
    if m.num_shared:
        params["shared"], pspecs["shared"] = layers.init_mlp(
            ks[2], d, m.num_shared * f, cfg.activation, dtype)
    if m.dense_residual:
        params["dense"], pspecs["dense"] = layers.init_mlp(
            ks[3], d, cfg.d_ff, cfg.activation, dtype)
    return params, pspecs


def _route(params, x2d: jnp.ndarray, cfg: ModelConfig):
    """x2d: (T, D) -> top-k ids/weights + aux loss terms (fp32)."""
    m = cfg.moe
    logits = (x2d.astype(jnp.float32) @ params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    top_p, top_ids = jax.lax.top_k(probs, m.top_k)              # (T, k)
    top_w = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    # Switch-style load-balance loss
    assign = jnp.zeros_like(probs).at[
        jnp.arange(x2d.shape[0])[:, None], top_ids].add(1.0)
    frac_assign = jnp.mean(assign, axis=0) / m.top_k            # (E,)
    frac_prob = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(frac_assign * frac_prob)
    return top_ids, top_w, aux, frac_assign


def _expert_ffn(bank, x, cfg: ModelConfig, idx=None):
    """x: (E, C, D) (or (C, D) with idx) through the expert MLPs."""
    take = (lambda w: w[idx]) if idx is not None else (lambda w: w)
    up = jnp.einsum("...cd,...df->...cf", x, take(bank["wi_up"]))
    if cfg.activation == "swiglu":
        gate = jnp.einsum("...cd,...df->...cf", x, take(bank["wi_gate"]))
        h = jax.nn.silu(gate) * up
    elif cfg.activation == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("...cf,...fd->...cd", h, take(bank["wo"]))


def _extras(params, x, cfg: ModelConfig):
    """Shared experts + dense residual (dense compute, model-sharded F)."""
    y = jnp.zeros_like(x)
    if "shared" in params:
        y = y + layers.mlp(params["shared"], x, cfg.activation)
    if "dense" in params:
        y = y + layers.mlp(params["dense"], x, cfg.activation)
    return y


# ---------------------------------------------------------------------------
# Oracle path
# ---------------------------------------------------------------------------

def moe_dense(params, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, MoEAux]:
    """Capacity-free reference: all experts on all tokens."""
    B, S, D = x.shape
    m = cfg.moe
    x2d = x.reshape(-1, D)
    top_ids, top_w, aux, load = _route(params, x2d, cfg)
    all_out = _expert_ffn(params["experts"], x2d[None].repeat(m.num_experts, 0),
                          cfg)                                   # (E, T, D)
    gathered = all_out[top_ids.T, jnp.arange(x2d.shape[0])[None]]  # (k, T, D)
    y = jnp.einsum("ktd,tk->td", gathered, top_w.astype(x.dtype))
    y = y.reshape(B, S, D) + _extras(params, x, cfg)
    return y, MoEAux(aux_loss=aux, load=load, dropped=jnp.zeros(()))


# ---------------------------------------------------------------------------
# Production path
# ---------------------------------------------------------------------------

def _moe_local(params_local, x_loc, cfg: ModelConfig, e_start: int,
               e_local: int, capacity: int, model_axis, fsdp_axis):
    """Per-device body (runs under shard_map, or standalone when unsharded).

    x_loc: (T, D) local tokens (replicated over 'model').
    params_local: expert bank local to this model rank; if ``fsdp_axis``,
    weights arrive D-sharded and are all-gathered here.
    """
    m = cfg.moe
    T, D = x_loc.shape
    bank = params_local["experts"]
    if fsdp_axis is not None:
        bank = {k: jax.lax.all_gather(
            w, fsdp_axis, axis=(2 if k == "wo" else 1), tiled=True)
            for k, w in bank.items()}

    top_ids, top_w, aux, load = _route(params_local, x_loc, cfg)
    flat_ids = top_ids.reshape(-1)                       # (T*k,)
    flat_w = top_w.reshape(-1)
    mine = (flat_ids >= e_start) & (flat_ids < e_start + e_local)
    le = jnp.clip(flat_ids - e_start, 0, e_local - 1)    # local expert id
    # intra-expert slot via masked cumsum
    onehot = (jax.nn.one_hot(le, e_local, dtype=jnp.int32)
              * mine[:, None].astype(jnp.int32))         # (T*k, E_loc)
    slot = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.sum(slot * onehot, axis=-1)               # (T*k,)
    keep = mine & (slot < capacity)
    dropped_here = jnp.sum(mine & ~keep).astype(jnp.float32)

    tok_idx = jnp.arange(T).repeat(m.top_k)
    slot_c = jnp.where(keep, slot, capacity)             # overflow row
    buf = jnp.zeros((e_local, capacity + 1, D), x_loc.dtype)
    buf = buf.at[le, slot_c].set(
        jnp.where(keep[:, None], x_loc[tok_idx], 0.0))
    out_buf = _expert_ffn(bank, buf[:, :capacity], cfg)  # (E_loc, C, D)
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((e_local, 1, D), out_buf.dtype)], axis=1)
    y_tok = out_buf[le, slot_c] * jnp.where(keep, flat_w, 0.0)[:, None].astype(x_loc.dtype)
    y = jnp.zeros_like(x_loc).at[tok_idx].add(y_tok)

    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
        dropped_here = jax.lax.psum(dropped_here, model_axis)
    dropped = dropped_here / (T * m.top_k)
    return y, MoEAux(aux_loss=aux, load=load, dropped=dropped)


def _moe_local_mapped(params_local, x_loc, cfg, e_start, e_local, capacity,
                      model_axis, fsdp_axis):
    """shard_map body wrapper: aux terms get a leading mapped batch dim of 1
    (per-data-shard values are NOT replicated, so they must be mapped)."""
    y, aux = _moe_local(params_local, x_loc, cfg, e_start, e_local, capacity,
                        model_axis, fsdp_axis)
    return y, MoEAux(aux_loss=aux.aux_loss[None], load=aux.load[None],
                     dropped=aux.dropped[None])


def moe_sharded(params, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, MoEAux]:
    """EP over 'model', token-parallel over batch axes, FSDP over 'data'."""
    B, S, D = x.shape
    m = cfg.moe
    mesh = shardctx.current_mesh()
    x2d = x.reshape(-1, D)

    if mesh is None or "model" not in mesh.axis_names:
        cap = int(math.ceil(x2d.shape[0] * m.top_k / m.num_experts
                            * m.capacity_factor))
        y, aux = _moe_local(params, x2d, cfg, 0, m.num_experts, cap,
                            None, None)
        y = y + _extras(params, x2d, cfg)
        return y.reshape(B, S, D), aux

    n_model = mesh.shape["model"]
    bat = shardctx.batch_axes() or None
    n_bat = 1
    for a in (bat or ()):
        n_bat *= mesh.shape[a]
    e_local = m.num_experts // n_model
    t_local = (B * S) // n_bat
    capacity = int(math.ceil(t_local * m.top_k / m.num_experts
                             * m.capacity_factor))
    has_fsdp = "data" in mesh.axis_names and mesh.shape["data"] > 1

    expert_specs = {k: P("model", "data", None) if k != "wo"
                    else P("model", None, "data")
                    for k in params["experts"]}
    if not has_fsdp:
        expert_specs = {k: P("model", None, None) for k in params["experts"]}
    pspec_in = {
        "router": P(None, None),
        "experts": expert_specs,
    }
    routed = {"router": params["router"], "experts": params["experts"]}

    def body(params_l, x_l):
        e_start = jax.lax.axis_index("model") * e_local
        return _moe_local_mapped(params_l, x_l, cfg, e_start, e_local,
                                 capacity, "model",
                                 "data" if has_fsdp else None)

    aux_spec = MoEAux(aux_loss=P(bat), load=P(bat, None), dropped=P(bat))
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec_in, P(bat, None)),
        out_specs=(P(bat, None), aux_spec),
        check_vma=False,
    )(routed, x2d)
    # always-on branches (shared experts / arctic dense residual) run as
    # plain GSPMD matmuls outside the expert shard_map — they are dense
    # compute, and XLA can overlap them with the routed path
    y = y + _extras(params, x2d, cfg)
    aux = MoEAux(aux_loss=jnp.mean(aux.aux_loss),
                 load=jnp.mean(aux.load, axis=0),
                 dropped=jnp.mean(aux.dropped))
    return y.reshape(B, S, D), aux


def moe_forward(params, x, cfg: ModelConfig,
                production: bool = True) -> Tuple[jnp.ndarray, MoEAux]:
    if production and shardctx.current_mesh() is not None:
        return moe_sharded(params, x, cfg)
    return moe_dense(params, x, cfg)
