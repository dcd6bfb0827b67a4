"""Per-arch smoke + decode/full-forward agreement + kernel-path parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import transformer as T

RT = T.Runtime(production=False, remat=True)


def _batch(cfg, B=2, S=48, dtype=jnp.bfloat16, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    b = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    if cfg.encoder_layers:
        b["audio_embeds"] = jax.random.normal(ks[1], (B, 24, cfg.d_model),
                                              dtype)
    if cfg.vision_stub:
        b["vision_embeds"] = jax.random.normal(ks[2], (B, 16, cfg.d_model),
                                               dtype)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    """Reduced config: one forward + one grad step, shapes + no NaNs."""
    cfg = get_config(arch, reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)

    loss, metrics = jax.jit(
        lambda p, b: T.loss_fn(p, b, cfg, RT))(params, batch)
    assert loss.shape == ()
    assert not bool(jnp.isnan(loss))

    grads = jax.grad(lambda p: T.loss_fn(p, batch, cfg, RT)[0])(params)
    gn = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0

    logits, _ = T.logits_fn(params, batch, cfg, RT)
    assert logits.shape == (2, 48, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_full_forward(arch):
    """Teacher-forced decode off the ring cache == full-sequence logits."""
    cfg = get_config(arch, reduced=True).replace(dtype="float32")
    rt = T.Runtime(production=False, remat=False)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    batch = _batch(cfg, B, S, jnp.float32)
    toks = batch["tokens"]
    full, _ = T.logits_fn(params, batch, cfg, rt)
    P0 = S - 3
    pb = dict(batch)
    pb["tokens"] = toks[:, :P0]
    lg, st = T.prefill(params, pb, cfg, rt, window=S)
    errs = [float(jnp.max(jnp.abs(lg - full[:, P0 - 1])))]
    for t in range(P0, S):
        lg, st = T.decode_step(params, st, toks[:, t:t + 1], cfg, rt)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, t]))))
    assert max(errs) < 1e-3, errs


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if not get_config(a).encoder_layers
                                  and not get_config(a).vision_stub])
def test_reference_logits_matches_full_forward(arch):
    """The layer-at-a-time float32 reference == logits_fn in float32."""
    cfg = get_config(arch, reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    toks = _batch(cfg, 2, 24)["tokens"]
    ref = T.reference_logits(params, toks, cfg)
    assert ref.dtype == jnp.float32 and ref.shape == (2, 24, cfg.vocab_size)
    cfg32 = cfg.replace(dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        full, _ = T.logits_fn(p32, {"tokens": toks}, cfg32,
                              T.Runtime(production=False, remat=False))
    scale = float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(ref - full))) <= 1e-5 * scale


@pytest.mark.parametrize("arch", ["qwen3-14b", "recurrentgemma-9b",
                                  "falcon-mamba-7b"])
def test_pallas_kernel_path_matches_jnp(arch):
    cfg = get_config(arch, reduced=True).replace(dtype="float32")
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, dtype=jnp.float32)
    l0, _ = T.loss_fn(params, batch, cfg,
                      T.Runtime(production=False, remat=False))
    l1, _ = T.loss_fn(params, batch, cfg,
                      T.Runtime(production=False, remat=False,
                                use_kernels=True, q_block=32, kv_block=32))
    assert abs(float(l0) - float(l1)) < 2e-4


def test_sliding_window_limits_context():
    """With window W, logits at position t ignore tokens < t - W."""
    cfg = get_config("qwen3-14b", reduced=True).replace(
        dtype="float32", attn_window=8, num_layers=2)
    rt = T.Runtime(production=False, remat=False)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                              cfg.vocab_size)
    out1, _ = T.logits_fn(params, {"tokens": toks}, cfg, rt)
    # perturb a token far outside the window of the last position
    toks2 = toks.at[0, 2].set((toks[0, 2] + 7) % cfg.vocab_size)
    out2, _ = T.logits_fn(params, {"tokens": toks2}, cfg, rt)
    # last position: tokens < 24-8 = 16 are invisible (2 < 16)
    assert float(jnp.max(jnp.abs(out1[0, -1] - out2[0, -1]))) < 1e-5
    # but position 3 (inside its window) must change
    assert float(jnp.max(jnp.abs(out1[0, 3] - out2[0, 3]))) > 1e-5


def test_moe_dense_vs_sharded_single_device():
    """The capacity-buffer production path == capacity-free oracle when
    capacity is ample (single device, no mesh)."""
    from repro.models import moe as M
    cfg = get_config("deepseek-moe-16b", reduced=True).replace(dtype="float32")
    cfg = cfg.replace(moe=cfg.moe.__class__(
        num_experts=8, top_k=2, d_ff_expert=64, num_shared=2,
        capacity_factor=8.0))
    params, _ = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y_ref, aux_ref = M.moe_dense(params, x, cfg)
    y_prod, aux_prod = M.moe_sharded(params, x, cfg)
    assert float(jnp.max(jnp.abs(y_ref - y_prod))) < 1e-4
    assert float(aux_prod.dropped) == 0.0
