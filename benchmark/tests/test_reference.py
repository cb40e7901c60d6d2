"""The plain GPT-2 agrees with ``TransformerLM`` at a tiny size on the CPU:
train path (forward, loss, gradients, one AdamW step) and serve path
(chunked prefill, then decode through the paged pool)."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.drivers import common
from benchmark.reference import gpt2 as ref

FIX = Path(__file__).resolve().parent / "fixtures"
CONFIG = json.loads((FIX / "configs" / "tiny.json").read_text())
SEED = 2**31 + 3


def _program(dtype=None):
    from rocket_tpu.models.transformer import TransformerLM

    tcfg = common.transformer_config(CONFIG)
    tcfg.activation_dtype = dtype
    tcfg.loss_chunk = 0
    return TransformerLM(tcfg)


def test_forward_loss_gradients_and_one_adamw_step_agree_with_transformer_lm():
    from rocket_tpu.models.transformer import next_token_loss

    model = _program()
    stacked = ref.make_params(ref.seed_key(SEED), CONFIG, jnp.float32)
    params = ref.unstack(stacked, CONFIG["n_layer"])
    tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, CONFIG["vocab_size"])
    objective = next_token_loss()

    def program_loss(p):
        out, _ = model.apply({"params": p, "state": {}}, {"tokens": tokens}, mode="train")
        return objective(out)

    with jax.default_matmul_precision("highest"):
        out, _ = model.apply({"params": params, "state": {}}, {"tokens": tokens}, mode="eval")
        want_loss, want_grads = jax.value_and_grad(program_loss)(params)
    got_logits = ref.logits(stacked, tokens, CONFIG["n_head"])
    np.testing.assert_allclose(got_logits, out["logits"], atol=2e-5)

    got_loss, got_grads = ref.loss_and_grads(stacked, tokens, CONFIG["n_head"], block_rows=2)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    got_grads = ref.unstack(got_grads, CONFIG["n_layer"])
    for name, g, w in zip(ref.leaf_names(want_grads), jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=2e-6, err_msg=name)

    # One AdamW step at a non-zero learning rate against optax's, with the
    # program's decay mask (two or more dims).
    tx = optax.adamw(1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.1,
                     mask=lambda p: jax.tree.map(lambda a: a.ndim >= 2, p))
    # (Both get the reference's gradients: a first Adam step is g / |g|,
    # which no tolerance on the gradients carries through.)
    stacked_grads = ref.loss_and_grads(stacked, tokens, CONFIG["n_head"], block_rows=4)[1]
    updates, _ = tx.update(
        ref.unstack(stacked_grads, CONFIG["n_layer"]), tx.init(params), params
    )
    want_params = optax.apply_updates(params, updates)
    got_params, _ = ref.adamw_update(
        stacked, stacked_grads, ref.adamw_init(stacked), 0, 1e-3, weight_decay=0.1,
    )
    got_params = ref.unstack(got_params, CONFIG["n_layer"])
    for name, g, w in zip(ref.leaf_names(want_params), jax.tree.leaves(got_params),
                          jax.tree.leaves(want_params)):
        np.testing.assert_allclose(g, w, atol=1e-6, err_msg=name)


def test_warmup_cosine_agrees_with_the_programs_schedule():
    from rocket_tpu import optim

    sched = optim.warmup_cosine_lr(3e-4, warmup_steps=100, decay_steps=1000)
    for step in (0, 1, 2, 50, 100, 101, 550, 999, 1000, 2000):
        got = ref.warmup_cosine(step, peak=3e-4, warmup_steps=100, decay_steps=1000)
        np.testing.assert_allclose(got, sched(step), rtol=1e-5, atol=1e-12)


def test_prefill_then_paged_decode_agrees_with_the_reference():
    from rocket_tpu.serve import ServeConfig

    model = _program()
    stacked = ref.make_params(ref.seed_key(SEED), CONFIG, jnp.float32)
    params = ref.unstack(stacked, CONFIG["n_layer"])
    tokens = np.asarray(
        jax.random.randint(jax.random.key(2), (40,), 0, CONFIG["vocab_size"]), np.int32
    )
    spec, mb, _, _ = ServeConfig(max_slots=1, block_len=16, max_model_len=48).resolve(model.config)
    k_pages, v_pages = spec.init_pages()
    table = jnp.asarray(1 + np.arange(mb, dtype=np.int32)[None])
    at = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
    step = jax.jit(model.decode_step_paged)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(stacked, jnp.asarray(tokens)[None], CONFIG["n_head"])[0]
        # Two prefill chunks of 16, then one token at a time.
        for start in (0, 16):
            _, k_pages, v_pages = step(
                params, jnp.asarray(tokens[None, start:start + 16]), k_pages, v_pages,
                table, at(start), at(16),
            )
        for pos in range(32, 40):
            got, k_pages, v_pages = step(
                params, jnp.asarray(tokens[None, pos:pos + 1]), k_pages, v_pages,
                table, at(pos), at(1),
            )
            np.testing.assert_allclose(got[0], want[pos], atol=5e-5, err_msg=str(pos))


def test_fp8_rounds_coarser_than_bfloat16():
    x = jax.random.normal(jax.random.key(0), (4096,)) * 0.02
    err8 = jnp.abs(ref.fp8(x) - x).mean()
    err16 = jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).mean()
    assert err8 > 8 * err16
    assert float(jnp.max(jnp.abs(ref.fp8(x)))) <= float(jnp.max(jnp.abs(x))) * 1.001


def test_seed_key_takes_seeds_past_32_bits():
    a = jax.random.key_data(ref.seed_key(2**31 + 5))
    b = jax.random.key_data(ref.seed_key(5))
    c = jax.random.key_data(ref.seed_key(2**31 + 5))
    assert not np.array_equal(a, b) and np.array_equal(a, c)
