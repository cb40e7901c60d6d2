"""Decompose GPT-2 124M single-chip step time to target MFU work.

Times the full fused train step and isolated pieces (attention fwd+bwd,
logits+loss fwd+bwd, one MLP matmul) so optimization effort lands where the
time actually is. Run on the real TPU chip: ``python scripts/profile_gpt2.py``.
Timings sync with ``jax.block_until_ready`` at the window edges.
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
import optax

V5E_BF16_PEAK = 197e12


def sync(tree):
    """Wait until every leaf of ``tree`` is computed (dispatch is async)."""
    jax.block_until_ready(tree)


def timeit(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / iters


def main():
    B, T, D, H, V, L = 8, 1024, 768, 12, 50257, 12
    key = jax.random.key(0)

    # --- full train step through the framework ---------------------------
    import rocket_tpu as rt
    from rocket_tpu import optim
    from rocket_tpu.core.module import Module
    from rocket_tpu.models.transformer import (
        TransformerConfig, TransformerLM, next_token_loss,
    )
    from rocket_tpu.runtime.context import Runtime

    runtime = Runtime(seed=0)
    config = TransformerConfig.gpt2_124m()
    model = TransformerLM(config)
    module = Module(
        model,
        capsules=[rt.Loss(next_token_loss()), rt.Optimizer(optim.adamw(), learning_rate=3e-4)],
        compute_dtype=jnp.bfloat16,
        runtime=runtime,
    )
    module.setup()
    tokens = np.random.default_rng(0).integers(0, V, (B, T)).astype(np.int32)
    batch = {"tokens": jax.device_put(tokens)}

    state = module.prepared.state
    step = module._train_step

    for _ in range(3):
        state, metrics = step(state, batch)
    sync(metrics["loss"])
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, batch)
    sync(metrics["loss"])
    dt = (time.perf_counter() - t0) / iters
    tok_s = B * T / dt
    flops = 6 * 124e6 * B * T + 12 * L * B * T * T * D
    print(f"full train step: {dt*1e3:.2f} ms  {tok_s:,.0f} tok/s  "
          f"~{flops/dt/1e12:.1f} TFLOP/s  MFU={flops/dt/V5E_BF16_PEAK:.1%}")

    # --- attention fwd+bwd -------------------------------------------------
    from rocket_tpu.nn.attention import dot_product_attention

    q = jax.random.normal(key, (B, H, T, D // H), jnp.bfloat16)
    k2 = jax.random.normal(key, (B, H, T, D // H), jnp.bfloat16)
    v2 = jax.random.normal(key, (B, H, T, D // H), jnp.bfloat16)

    @jax.jit
    def attn_fwd(q, k, v):
        return dot_product_attention(q, k, v, causal=True)

    @jax.jit
    def attn_bwd(q, k, v):
        return jax.grad(
            lambda q, k, v: dot_product_attention(q, k, v, causal=True)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    dt_f = timeit(attn_fwd, q, k2, v2)
    dt_b = timeit(attn_bwd, q, k2, v2)
    attn_flops = 4 * B * H * T * T * (D // H)
    print(f"attention fwd: {dt_f*1e3:.2f} ms ({attn_flops/dt_f/1e12:.1f} TFLOP/s eff)  "
          f"bwd+fwd: {dt_b*1e3:.2f} ms; x{L} layers = {L*(dt_f+dt_b)*1e3:.1f} ms")

    # --- logits + loss fwd+bwd --------------------------------------------
    x = jax.random.normal(key, (B, T, D), jnp.bfloat16)
    wte = jax.random.normal(key, (V, D), jnp.float32)
    targets = jnp.asarray(tokens)

    @jax.jit
    def loss_fn(x, wte):
        logits = jnp.einsum("btd,vd->btv", x, wte.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), targets[:, 1:]
        ).mean()

    @jax.jit
    def loss_bwd(x, wte):
        return jax.grad(loss_fn, argnums=(0, 1))(x, wte)

    dt_lf = timeit(loss_fn, x, wte)
    dt_lb = timeit(loss_bwd, x, wte)
    logit_flops = 2 * B * T * D * V
    print(f"logits+loss fwd (FULL, not what gpt2_124m runs): "
          f"{dt_lf*1e3:.2f} ms ({logit_flops/dt_lf/1e12:.1f} TFLOP/s)  "
          f"fwd+bwd: {dt_lb*1e3:.2f} ms ({3*logit_flops/dt_lb/1e12:.1f} TFLOP/s)")

    # --- chunked head+CE (loss_chunk — the production gpt2_124m path) ------
    from rocket_tpu.models.transformer import _chunked_next_token_nll

    @jax.jit
    def chunked_fn(x, wte):
        return _chunked_next_token_nll(
            x, targets, 128,
            lambda xc: jnp.einsum("bcd,vd->bcv", xc, wte.astype(xc.dtype)),
        )

    @jax.jit
    def chunked_bwd(x, wte):
        return jax.grad(chunked_fn, argnums=(0, 1))(x, wte)

    dt_cf = timeit(chunked_fn, x, wte)
    dt_cb = timeit(chunked_bwd, x, wte)
    print(f"chunked head+CE fwd: {dt_cf*1e3:.2f} ms "
          f"({logit_flops/dt_cf/1e12:.1f} TFLOP/s)  "
          f"fwd+bwd: {dt_cb*1e3:.2f} ms "
          f"({3*logit_flops/dt_cb/1e12:.1f} TFLOP/s model-flops)")

    # --- one MLP matmul pair ----------------------------------------------
    w1 = jax.random.normal(key, (D, 4 * D), jnp.bfloat16)
    w2 = jax.random.normal(key, (4 * D, D), jnp.bfloat16)

    @jax.jit
    def mlp(x, w1, w2):
        return jax.nn.gelu(x @ w1) @ w2

    dt_m = timeit(mlp, x, w1, w2)
    mlp_flops = 2 * B * T * D * 4 * D * 2
    print(f"mlp fwd: {dt_m*1e3:.2f} ms ({mlp_flops/dt_m/1e12:.1f} TFLOP/s eff); "
          f"x{L} = {L*dt_m*1e3:.1f} ms fwd only")


if __name__ == "__main__":
    main()
