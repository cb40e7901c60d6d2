"""rocket_tpu.serve — paged KV pool, compiled-once engine, continuous batching.

The load-bearing assertions:

* block-pool alloc/free invariants (no double alloc/free, reserved trash
  block, all-or-nothing allocation, zero external fragmentation);
* chunked prefill == one-shot prefill logits (same compiled code path at
  any chunk size);
* admitting/evicting/refilling requests across a 50-request workload
  causes ZERO decode-step retraces (trace counters + the obs registry
  gauge) — the compiled-once guarantee of ISSUE 7;
* EOS, per-slot sampling params, eviction under a starved pool, and the
  e2e outputs matching ``generate()`` greedy token-for-token.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rocket_tpu.models.transformer import TransformerConfig, TransformerLM, generate
from rocket_tpu.serve import (
    BlockAllocator,
    KVPoolSpec,
    ServeConfig,
    ServeEngine,
)


@pytest.fixture(scope="module")
def tiny_lm():
    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(0))
    return model, variables


@pytest.fixture(scope="module")
def llama_lm():
    """RoPE + RMSNorm + GQA + untied head — the other cache geometry."""
    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, pos_embedding="rope", norm="rmsnorm", mlp="swiglu",
        tied_embeddings=False, dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(1))
    return model, variables


@pytest.fixture(scope="module")
def scan_lm():
    """``scan_layers=True``: the paged step scans the blocks with the whole
    pool in the carry and the layer index as a traced scalar."""
    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=3, num_heads=4,
        num_kv_heads=2, dropout=0.0, scan_layers=True,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(2))
    return model, variables


# ---------------------------------------------------------------------------
# Block pool
# ---------------------------------------------------------------------------

def test_block_allocator_invariants():
    alloc = BlockAllocator(8)  # blocks 1..7 allocatable, 0 reserved
    assert alloc.capacity == 7
    a = alloc.alloc(3)
    b = alloc.alloc(4)
    assert sorted(a + b) == list(range(1, 8))  # block 0 never handed out
    assert alloc.alloc(1) is None              # exhausted -> None, not raise
    assert alloc.num_free == 0 and alloc.free_fraction == 0.0
    alloc.free(a)
    assert alloc.num_free == 3 and alloc.free_fraction == pytest.approx(3 / 7)
    # All-or-nothing: asking for more than free allocates NOTHING.
    assert alloc.alloc(4) is None
    assert alloc.num_free == 3
    # Any free block serves any request — no external fragmentation: the
    # freed ids are immediately reusable regardless of original grouping.
    c = alloc.alloc(3)
    assert sorted(c) == sorted(a)
    with pytest.raises(ValueError):
        alloc.free([c[0], c[0]])  # double free
    with pytest.raises(ValueError):
        alloc.free([0])           # reserved trash block
    with pytest.raises(ValueError):
        BlockAllocator(1)


def test_kv_pool_spec_bytes_and_pages():
    spec = KVPoolSpec(num_layers=2, num_blocks=5, block_len=4,
                      num_kv_heads=3, head_dim=8, dtype="bfloat16")
    assert spec.block_bytes == 2 * 2 * 4 * 3 * 8 * 2
    assert spec.pool_bytes == 5 * spec.block_bytes
    k, v = spec.init_pages()
    assert k.shape == v.shape == spec.pages_shape == (2, 5, 4, 3 * 8)
    assert k.dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        KVPoolSpec(num_layers=1, num_blocks=1, block_len=4,
                   num_kv_heads=1, head_dim=8)


# ---------------------------------------------------------------------------
# Paged decode correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lm", ["tiny_lm", "llama_lm", "scan_lm"])
@pytest.mark.parametrize("chunk", [3, 16])
def test_chunked_prefill_matches_one_shot_logits(lm, chunk, request):
    """Prefill through the paged path in chunks of any size must produce
    the SAME last-position logits as the dense full-prompt forward — the
    chunked/one-shot equivalence that lets prefill interleave with decode."""
    model, variables = request.getfixturevalue(lm)
    p = variables["params"]
    b, plen = 3, 9
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, size=(b, plen)).astype(np.int32)

    out, _ = model.apply(
        {"params": p, "state": {}}, {"tokens": jnp.asarray(prompt)},
        mode="eval",
    )
    ref = np.asarray(out["logits"][:, -1].astype(jnp.float32))

    cfg = model.config
    h_kv = cfg.num_kv_heads or cfg.num_heads
    bl, mb = 4, 8
    spec = KVPoolSpec(num_layers=cfg.num_layers, num_blocks=1 + b * mb,
                      block_len=bl, num_kv_heads=h_kv,
                      head_dim=cfg.dim // cfg.num_heads)
    kp, vp = spec.init_pages()
    table = np.zeros((b, mb), np.int32)
    for s in range(b):
        table[s] = 1 + s * mb + np.arange(mb)
    table = jnp.asarray(table)

    # Chunked prefill of [0, plen-1) ...
    for start in range(0, plen - 1, chunk):
        piece = prompt[:, start:min(start + chunk, plen - 1)]
        valid = np.full((b,), piece.shape[1], np.int32)
        if piece.shape[1] < chunk:
            piece = np.pad(piece, ((0, 0), (0, chunk - piece.shape[1])))
        _, kp, vp = model.decode_step_paged(
            p, jnp.asarray(piece), kp, vp, table,
            jnp.full((b,), start, jnp.int32), jnp.asarray(valid),
        )
    # ... then the last prompt token through the C=1 decode shape.
    logits, kp, vp = model.decode_step_paged(
        p, jnp.asarray(prompt[:, -1:]), kp, vp, table,
        jnp.full((b,), plen - 1, jnp.int32), jnp.ones((b,), jnp.int32),
    )
    got = np.asarray(logits.astype(jnp.float32))
    np.testing.assert_allclose(got, ref, atol=1e-4)


# ---------------------------------------------------------------------------
# Engine: compiled-once + lifecycle
# ---------------------------------------------------------------------------

def _greedy_reference(model, variables, prompt, max_new):
    full = generate(model, variables, prompt[None, :], max_new, temperature=0)
    return np.asarray(full)[0, len(prompt):]


def test_no_retrace_across_admission(tiny_lm):
    """Admitting/evicting/refilling across a full 50-request synthetic
    workload compiles the decode step and the prefill step exactly ONCE,
    asserted both on the engine's trace counters and on the obs registry
    gauges telemetry.json would carry."""
    from rocket_tpu.obs.telemetry import Telemetry

    model, variables = tiny_lm
    telemetry = Telemetry(enabled=True)
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=48),
        telemetry=telemetry,
    )
    rng = np.random.default_rng(11)
    rids, prompts, maxnews = [], [], []
    for _ in range(50):
        plen = int(rng.integers(1, 14))
        maxnew = int(rng.integers(1, 9))
        prompt = rng.integers(0, 64, size=plen).astype(np.int32)
        prompts.append(prompt)
        maxnews.append(maxnew)
        rids.append(engine.submit(prompt, max_new_tokens=maxnew,
                                  temperature=0.0))
    engine.drain()
    report = engine.report()
    assert report["requests"]["completed"] == 50
    assert report["compiled"]["decode_traces"] == 1, report["compiled"]
    assert report["compiled"]["prefill_traces"] == 1, report["compiled"]
    # The registry carries the same proof (what serve_smoke greps out of
    # telemetry.json in CI).
    gauges = telemetry.registry.snapshot()["gauges"]
    assert gauges["serve/decode_traces"] == 1
    assert gauges["serve/prefill_traces"] == 1
    assert gauges["serve/requests_completed"] == 50
    # Pool HBM is slot-count math, not request-count math.
    assert gauges["serve/kv_pool_bytes"] == engine.engine.spec.pool_bytes

    # e2e correctness: every request's tokens == the generate() greedy
    # reference for its prompt.
    for rid, prompt, maxnew in zip(rids, prompts, maxnews):
        ref = _greedy_reference(model, variables, prompt, maxnew)
        got = np.asarray(engine.result(rid).tokens, np.int32)
        np.testing.assert_array_equal(got, ref, err_msg=f"request {rid}")
    # Per-request spans landed in the trace.
    names = [e[0] for e in telemetry.spans.events()]
    assert sum(1 for n in names if n.startswith("serve/request[")) == 50


def test_eos_finishes_early_and_frees_slot(tiny_lm):
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=2, block_len=4, prefill_chunk=4,
                    max_model_len=32),
    )
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    ref = _greedy_reference(model, variables, prompt, 6)
    eos = int(ref[2])
    # The request must stop at the FIRST greedy occurrence of eos.
    first = int(np.nonzero(ref == eos)[0][0])
    rid = engine.submit(prompt, max_new_tokens=6, temperature=0.0,
                        eos_token_id=eos)
    engine.drain()
    req = engine.result(rid)
    assert req.tokens == [int(t) for t in ref[:first + 1]]
    assert req.tokens[-1] == eos
    assert len(req.tokens) < 6  # actually finished early
    # Slot + blocks released.
    assert engine.scheduler.active_slots == 0
    assert engine.scheduler.allocator.free_fraction == 1.0


def test_eviction_backpressure_and_resume(tiny_lm):
    """A pool too small for the offered load must preempt the youngest
    request (blocks freed, request re-queued) and still finish EVERY
    request with outputs identical to the uncontended reference."""
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=32, num_blocks=9),  # 8 allocatable
    )
    rng = np.random.default_rng(3)
    rids, prompts, maxnews = [], [], []
    for _ in range(8):
        plen = int(rng.integers(4, 12))
        maxnew = int(rng.integers(8, 16))
        prompt = rng.integers(0, 64, size=plen).astype(np.int32)
        prompts.append(prompt)
        maxnews.append(maxnew)
        rids.append(engine.submit(prompt, max_new_tokens=maxnew,
                                  temperature=0.0))
    engine.drain()
    report = engine.report()
    assert report["requests"]["completed"] == 8
    assert report["requests"]["preemptions"] > 0
    assert report["compiled"]["decode_traces"] == 1
    for rid, prompt, maxnew in zip(rids, prompts, maxnews):
        ref = _greedy_reference(model, variables, prompt, maxnew)
        np.testing.assert_array_equal(
            np.asarray(engine.result(rid).tokens, np.int32), ref,
            err_msg=f"request {rid} diverged across preemption",
        )
    # Everything drained back to the pool.
    assert engine.scheduler.allocator.free_fraction == 1.0


def test_submit_validation(tiny_lm):
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=2, block_len=4, max_model_len=16,
                    num_blocks=4),  # capacity 3 < the 4 a full seq needs
    )
    with pytest.raises(ValueError):  # empty prompt
        engine.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError):  # exceeds per-slot context
        engine.submit(np.zeros((10,), np.int32), max_new_tokens=10)
    with pytest.raises(ValueError):  # needs more blocks than the pool has
        engine.submit(np.zeros((8,), np.int32), max_new_tokens=8)
    with pytest.raises(ValueError):  # top_p <= 0 masks every token
        engine.submit(np.zeros((2,), np.int32), temperature=0.9, top_p=0.0)
    with pytest.raises(ValueError):  # oversized max_model_len vs model
        ServeEngine(model, variables["params"],
                    ServeConfig(max_model_len=1024))


def test_completed_request_retention_cap_and_release(tiny_lm):
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=2, block_len=4, prefill_chunk=4,
                    max_model_len=16, max_completed_requests=3),
    )
    rids = [engine.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)
            for _ in range(5)]
    engine.drain()
    # Only the newest 3 finished records survive the cap.
    assert [r for r in rids if r in engine.requests] == rids[2:]
    engine.release(rids[3])
    assert rids[3] not in engine.requests
    live = engine.submit(np.asarray([1], np.int32), max_new_tokens=4)
    with pytest.raises(ValueError):
        engine.release(live)  # still running
    engine.drain()
    # reset_metrics zeroes the aggregates but NEVER the trace counters.
    engine.reset_metrics()
    report = engine.report()
    assert report["tokens_generated"] == 0
    assert report["compiled"]["decode_traces"] == 1


def test_release_and_retention_drop_request_timelines(tiny_lm):
    """Timeline retention follows Request retention: release() and the
    max_completed_requests cap both drop the reqtrace record, so a
    week-long server keeps bounded timeline memory."""
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=2, block_len=4, prefill_chunk=4,
                    max_model_len=16, max_completed_requests=3),
    )
    rids = [engine.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)
            for _ in range(5)]
    engine.drain()
    # The cap evicted the two oldest timelines along with their Requests.
    assert engine.tracer.timeline(rids[0]) is None
    assert engine.tracer.timeline(rids[1]) is None
    kept = engine.tracer.timeline(rids[2])
    assert kept is not None and kept["final"] and kept["tokens"] == 2
    assert [e["ev"] for e in kept["events"]][0] == "submit"
    assert [e["ev"] for e in kept["events"]][-1] == "finish"
    engine.release(rids[2])
    assert rids[2] not in engine.requests
    assert engine.tracer.timeline(rids[2]) is None
    # Phase aggregate over what's retained still renders in report().
    assert engine.report()["phases"]["requests"] == 2


def test_reqtrace_overhead_bound_and_rejection_counter(tiny_lm):
    """The tracing contract: reqtrace on vs off drives IDENTICAL device
    work (same dispatch/wave/transfer counts, same outputs) — the
    recorder is host dicts only. Also pins submit-time rejections
    landing in serve/rejected_requests instead of vanishing."""
    model, variables = tiny_lm

    def run(reqtrace: bool):
        engine = ServeEngine(
            model, variables["params"],
            ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                        max_model_len=32, num_blocks=9, reqtrace=reqtrace),
        )
        rng = np.random.default_rng(7)
        rids = [
            engine.submit(
                rng.integers(0, 64, size=int(rng.integers(2, 10))).astype(
                    np.int32
                ),
                max_new_tokens=int(rng.integers(4, 10)),
            )
            for _ in range(8)
        ]
        engine.drain()
        outputs = [list(engine.result(rid).tokens) for rid in rids]
        eng = engine.engine
        return engine, outputs, (
            eng.decode_dispatches, eng.decode_waves, eng.device_gets,
            eng.prefill_chunks,
        )

    traced, out_on, counts_on = run(reqtrace=True)
    plain, out_off, counts_off = run(reqtrace=False)
    assert counts_on == counts_off, "reqtrace changed device work"
    assert out_on == out_off
    assert plain.tracer is None and traced.tracer is not None
    # Every request's timeline closed with the same token count.
    for rid, tokens in enumerate(out_on):
        rec = traced.tracer.timeline(rid)
        assert rec["final"] and rec["tokens"] == len(tokens)
        assert abs(sum(rec["phases"].values()) - rec["total_s"]) \
            <= 0.05 * rec["total_s"] + 1e-9
    # Preempted requests carry the eviction on their one timeline.
    assert traced.report()["requests"]["preemptions"] > 0
    evicted = [r for r in range(8)
               if traced.tracer.timeline(r)["preemptions"] > 0]
    assert evicted, "starved pool should have preempted someone"
    for rid in evicted:
        assert traced.tracer.timeline(rid)["phases"]["preempted_s"] > 0
    # Submit-time refusals count instead of vanishing.
    with pytest.raises(ValueError):
        traced.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError):
        traced.submit("text", max_new_tokens=2)  # no tokenizer attached
    assert traced.report()["requests"]["rejected"] == 2


def test_generate_accepts_numpy_integer_scalars(tiny_lm):
    """np.int64 scalars (rng.integers() output) must route to the scalar
    path, not be mistaken for per-sequence arrays."""
    model, variables = tiny_lm
    prompt = np.asarray([[1, 2, 3]], np.int32)
    a = np.asarray(generate(model, variables, prompt, 4, temperature=0))
    b = np.asarray(generate(model, variables, prompt, np.int64(4),
                            temperature=0, eos_token_id=np.int32(63)))
    np.testing.assert_array_equal(a.shape, b.shape)
    # numpy-integer top_k routes to the static lax.top_k path.
    c = np.asarray(generate(model, variables, prompt, 4,
                            key=jax.random.key(0), top_k=np.int32(1)))
    np.testing.assert_array_equal(c, a)  # k=1 forces the argmax


def test_streaming_and_per_slot_sampling(tiny_lm):
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=48),
    )
    prompt = np.asarray([1, 2, 3], np.int32)
    greedy_rid = engine.submit(prompt, max_new_tokens=5, temperature=0.0)
    sampled_rid = engine.submit(prompt, max_new_tokens=5, temperature=0.9,
                                top_k=8, top_p=0.9)
    streamed = list(engine.stream(greedy_rid))
    assert streamed == engine.result(greedy_rid).tokens
    np.testing.assert_array_equal(
        np.asarray(streamed, np.int32),
        _greedy_reference(model, variables, prompt, 5),
    )
    engine.drain()
    sampled = engine.result(sampled_rid).tokens
    assert len(sampled) == 5
    assert all(0 <= t < 64 for t in sampled)
    # Sampling knobs are RUNTIME arrays: mixing greedy and sampled slots
    # in one engine never caused a second trace.
    assert engine.engine.decode_traces == 1


def test_gqa_rope_model_serves(llama_lm):
    model, variables = llama_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=3, block_len=4, prefill_chunk=4,
                    max_model_len=48),
    )
    rng = np.random.default_rng(5)
    rids, prompts, maxnews = [], [], []
    for _ in range(7):
        plen = int(rng.integers(1, 10))
        maxnew = int(rng.integers(1, 7))
        prompt = rng.integers(0, 64, size=plen).astype(np.int32)
        prompts.append(prompt)
        maxnews.append(maxnew)
        rids.append(engine.submit(prompt, max_new_tokens=maxnew,
                                  temperature=0.0))
    engine.drain()
    for rid, prompt, maxnew in zip(rids, prompts, maxnews):
        np.testing.assert_array_equal(
            np.asarray(engine.result(rid).tokens, np.int32),
            _greedy_reference(model, variables, prompt, maxnew),
        )


# ---------------------------------------------------------------------------
# k-wave scanned dispatch (ISSUE 11)
# ---------------------------------------------------------------------------

def test_scanned_waves_bit_identical_greedy_and_one_sync_per_dispatch(tiny_lm):
    """The k-wave scan must change HOW tokens are produced (one dispatch
    + one device_get per k waves), never WHAT is produced: greedy
    outputs bit-identical to the k=1 engine across a mixed workload,
    with the decode program still compiled exactly once."""
    model, variables = tiny_lm

    def run(k):
        engine = ServeEngine(
            model, variables["params"],
            ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                        max_model_len=48, decode_waves_per_dispatch=k),
        )
        rng = np.random.default_rng(23)
        rids = []
        for _ in range(16):
            plen = int(rng.integers(1, 12))
            maxnew = int(rng.integers(1, 11))
            prompt = rng.integers(0, 64, size=plen).astype(np.int32)
            rids.append(engine.submit(prompt, max_new_tokens=maxnew,
                                      temperature=0.0))
        engine.drain()
        return engine, rids

    base, base_rids = run(1)
    for k in (3, 4):
        scan, scan_rids = run(k)
        for b, s in zip(base_rids, scan_rids):
            assert scan.result(s).tokens == base.result(b).tokens, \
                f"k={k} diverged on request {s}"
        eng = scan.engine
        assert eng.decode_traces == 1
        assert eng.prefill_traces == 1
        # One host sync per dispatch of k waves — the amortization.
        assert eng.device_gets == eng.decode_dispatches
        assert eng.decode_waves == k * eng.decode_dispatches
        assert eng.device_gets < base.engine.device_gets
    report = scan.report()
    assert report["dispatch"]["waves_per_dispatch"] == 4
    assert report["dispatch"]["device_get_count"] == \
        report["dispatch"]["decode_dispatches"]
    assert report["dispatch"]["tokens_per_dispatch"] > 1.0


def test_scan_eos_freezes_across_dispatch_boundary(tiny_lm):
    """A request whose EOS lands mid-scan must emit exactly up to the
    EOS — no trailing tokens from the dispatch's remaining waves — and
    one whose EOS falls ON a dispatch boundary must freeze into the
    next dispatch. Both must match the k=1 engine exactly."""
    model, variables = tiny_lm
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    ref = _greedy_reference(model, variables, prompt, 9)
    for eos_at in (1, 2, 3, 4):  # mid-scan and on-boundary for k=3
        eos = int(ref[eos_at])
        first = int(np.nonzero(ref == eos)[0][0])
        engine = ServeEngine(
            model, variables["params"],
            ServeConfig(max_slots=2, block_len=4, prefill_chunk=4,
                        max_model_len=32, decode_waves_per_dispatch=3),
        )
        rid = engine.submit(prompt, max_new_tokens=9, temperature=0.0,
                            eos_token_id=eos)
        engine.drain()
        got = engine.result(rid).tokens
        assert got == [int(t) for t in ref[:first + 1]], \
            f"eos_at={eos_at}: {got} vs {ref[:first + 1]}"
        assert engine.scheduler.active_slots == 0
        assert engine.scheduler.allocator.free_fraction == 1.0


def test_scanned_eviction_backpressure_resume_equivalence(tiny_lm):
    """Eviction-resume under a starved pool with the k-wave scan: every
    request still finishes with outputs identical to the uncontended
    reference, with zero retraces — preemption happens strictly between
    dispatches (harvest-before-evict), so no in-flight token is lost."""
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=32, num_blocks=9,
                    decode_waves_per_dispatch=3),
    )
    rng = np.random.default_rng(3)
    rids, prompts, maxnews = [], [], []
    for _ in range(8):
        plen = int(rng.integers(4, 12))
        maxnew = int(rng.integers(8, 16))
        prompt = rng.integers(0, 64, size=plen).astype(np.int32)
        prompts.append(prompt)
        maxnews.append(maxnew)
        rids.append(engine.submit(prompt, max_new_tokens=maxnew,
                                  temperature=0.0))
    engine.drain()
    report = engine.report()
    assert report["requests"]["completed"] == 8
    assert report["requests"]["preemptions"] > 0
    assert report["compiled"]["decode_traces"] == 1
    for rid, prompt, maxnew in zip(rids, prompts, maxnews):
        ref = _greedy_reference(model, variables, prompt, maxnew)
        np.testing.assert_array_equal(
            np.asarray(engine.result(rid).tokens, np.int32), ref,
            err_msg=f"request {rid} diverged across scanned preemption",
        )
    assert engine.scheduler.allocator.free_fraction == 1.0


# ---------------------------------------------------------------------------
# The pallas paged-decode kernel (ISSUE 11 tentpole)
# ---------------------------------------------------------------------------

def _paged_operands(s=3, hq=4, hkv=2, d=64, bl=16, mb=4, dtype=np.float32,
                    layers=1, wave=None):
    """One C=1 wave's operands against a pool in the stored layout,
    ``(layers, NB, BL, Hkv*D)`` (128 lanes unless told otherwise: the
    fused kernel's gate). ``wave``: ``[(position, valid), ...]`` a slot,
    ``None`` for a free one (position 0, valid 0, a table of zeros)."""
    if wave is not None:
        s = len(wave)
    rng = np.random.default_rng(11)
    nb = 1 + s * mb
    q = jnp.asarray(rng.normal(size=(s, 1, hq, d)).astype(np.float32)) \
        .astype(dtype)
    k_new = jnp.asarray(
        rng.normal(size=(s, 1, hkv, d)).astype(np.float32)
    ).astype(dtype)
    v_new = k_new * 0.5
    k_pages = jnp.asarray(
        rng.normal(size=(layers, nb, bl, hkv * d)).astype(np.float32)
    ).astype(dtype)
    v_pages = k_pages * 0.25
    table = jnp.asarray(
        1 + np.arange(s * mb, dtype=np.int32).reshape(s, mb)
    )
    # Positions spanning page-start, mid-page and the full context.
    positions = jnp.asarray([0, bl + 3, mb * bl - 1], jnp.int32)[:s]
    valid = jnp.ones((s,), jnp.int32)
    if wave is not None:
        free = jnp.asarray([slot is None for slot in wave])
        table = jnp.where(free[:, None], 0, table)
        positions, valid = (
            jnp.asarray([0 if slot is None else slot[i] for slot in wave],
                        jnp.int32) for i in (0, 1)
        )
    return q, k_new, v_new, k_pages, v_pages, table, positions, valid


def _dense_reference(q, k_new, v_new, k_pages, v_pages, table, positions,
                     valid, layer):
    """Plain numpy attention of each real chunk row over its slot's prefix:
    pool rows below the chunk, the chunk's own rows from ``k_new``/``v_new``.
    Returns ``(S, C, Hq*D)`` with padded rows left at zero."""
    q, k_new, v_new, k_pages, v_pages = (
        np.asarray(a, np.float64) for a in (q, k_new, v_new, k_pages, v_pages)
    )
    table, positions, valid = (np.asarray(a) for a in (table, positions, valid))
    s, c, hq, d = q.shape
    hkv = k_new.shape[2]
    bl = k_pages.shape[2]
    out = np.zeros((s, c, hq, d))
    for n in range(s):
        start = int(positions[n])
        for i in range(int(valid[n])):
            keys, vals = [], []
            for t in range(start + i + 1):
                if t >= start:
                    keys.append(k_new[n, t - start])
                    vals.append(v_new[n, t - start])
                else:
                    page = (layer, table[n, t // bl], t % bl)
                    keys.append(k_pages[page].reshape(hkv, d))
                    vals.append(v_pages[page].reshape(hkv, d))
            keys, vals = np.stack(keys), np.stack(vals)      # (T, Hkv, D)
            for h in range(hq):
                kv = h // (hq // hkv)
                logits = keys[:, kv] @ q[n, i, h] / np.sqrt(d)
                w = np.exp(logits - logits.max())
                out[n, i, h] = (w / w.sum()) @ vals[:, kv]
    return out.reshape(s, c, hq * d)


#: (Hq, Hkv, D): MHA at the chat cell's 20 x 64 (1280 lanes, ten lane
#: tiles), GQA with Hkv < Hq, and an Hkv*D that is no multiple of 128
#: (3 x 8 = 24 lanes: the XLA path's alone — the fused kernel copies
#: whole rows out of HBM, which Mosaic slices at 128 lanes only).
_LAYOUT_GEOMETRIES = {
    "mha_20x64": (20, 20, 64),
    "gqa_6over2": (6, 2, 64),
    "lanes_24": (3, 3, 8),
}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("geometry", list(_LAYOUT_GEOMETRIES))
def test_paged_attention_matches_dense_reference(geometry, impl):
    """Both implementations against plain attention, on the stored
    ``(L, NB, BL, Hkv*D)`` layout and at a layer that is not the first:
    the wave's rows land at ``(layer, block, row)``, every other row of
    the pool (other layers included) stays bitwise what it was."""
    from rocket_tpu.ops.paged_attention import paged_attention

    hq, hkv, d = _LAYOUT_GEOMETRIES[geometry]
    layer = 1
    ops = _paged_operands(hq=hq, hkv=hkv, d=d, layers=3)
    q, k_new, v_new, k_pages, v_pages, table, positions, valid = ops
    assert k_pages.shape == (3, 13, 16, hkv * d)
    if impl == "pallas" and (hkv * d) % 128:
        # A pinned kernel that cannot run is an error, never the other path.
        with pytest.raises(ValueError, match="cannot run here"):
            paged_attention(*ops, layer=layer, impl=impl, interpret=True)
        return
    out, kp, vp = paged_attention(
        *ops, layer=layer, impl=impl, interpret=impl == "pallas"
    )
    ref = _dense_reference(*ops, layer)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    assert kp.shape == k_pages.shape and vp.shape == v_pages.shape
    want_k, want_v = np.array(k_pages), np.array(v_pages)
    for n in range(3):
        pos = int(positions[n])
        at = (layer, int(table[n, pos // 16]), pos % 16)
        want_k[at] = np.asarray(k_new[n, 0]).reshape(-1)
        want_v[at] = np.asarray(v_new[n, 0]).reshape(-1)
    np.testing.assert_array_equal(np.asarray(kp), want_k)
    np.testing.assert_array_equal(np.asarray(vp), want_v)


@pytest.mark.parametrize("geometry", list(_LAYOUT_GEOMETRIES))
def test_paged_prefill_chunk_matches_dense_reference(geometry):
    """A C > 1 chunk (the prefill program's shape) with a padded tail:
    real rows match plain attention, padded rows write only to the trash
    block, and the layer may be a traced scalar (the scanned model's)."""
    from rocket_tpu.ops.paged_attention import paged_attention

    hq, hkv, d = _LAYOUT_GEOMETRIES[geometry]
    rng = np.random.default_rng(5)
    s, c, bl, mb, layers = 2, 5, 16, 3, 2
    _, _, _, k_pages, v_pages, _, _, _ = _paged_operands(
        s=s, hq=hq, hkv=hkv, d=d, mb=mb, layers=layers
    )
    table = jnp.asarray(1 + np.arange(s * mb, dtype=np.int32).reshape(s, mb))
    q = jnp.asarray(rng.normal(size=(s, c, hq, d)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(s, c, hkv, d)).astype(np.float32))
    v_new = k_new * 0.5
    positions = jnp.asarray([bl - 2, 7], jnp.int32)   # slot 0 crosses a page
    valid = jnp.asarray([5, 3], jnp.int32)            # slot 1: 2 padded rows
    ops = (q, k_new, v_new, k_pages, v_pages, table, positions, valid)

    out, kp, vp = jax.jit(
        lambda layer, *a: paged_attention(*a, layer=layer)
    )(jnp.int32(1), *ops)
    ref = _dense_reference(*ops, 1)
    real = np.arange(c)[None, :] < np.asarray(valid)[:, None]
    np.testing.assert_allclose(
        np.asarray(out)[real], ref[real], atol=2e-5, rtol=2e-5
    )
    want_k = np.array(k_pages)
    for n in range(s):
        for i in range(int(valid[n])):
            pos = int(positions[n]) + i
            want_k[1, int(table[n, pos // bl]), pos % bl] = \
                np.asarray(k_new[n, i]).reshape(-1)
    got_k = np.asarray(kp)
    np.testing.assert_array_equal(got_k[:, 1:], want_k[:, 1:])
    np.testing.assert_array_equal(got_k[0], want_k[0])   # layer 0 untouched
    np.testing.assert_array_equal(got_k[1, 0, 1:], want_k[1, 0, 1:])


#: Waves of ``(position, valid)`` a slot against 4 pages of 16 rows (None:
#: a free slot, its table zeros). A slot with ``valid`` 0 does not run:
#: free, or mid-prefill with a long context the wave must not pay for.
_FULL = 4 * 16 - 1
_WAVES = {
    "all_run": [(0, 1), (16 + 3, 1), (_FULL, 1)],
    "free_slot": [None, (16 + 3, 1), (_FULL, 1)],
    "valid0_long_context": [(_FULL - 1, 0), (5, 1), (_FULL, 1)],
    "context_of_1": [(0, 1), (0, 1), (0, 1)],
    "exactly_one_page": [(15, 1), (15, 1), (0, 1)],
    "one_page_plus_1_row": [(16, 1), (16, 1), (15, 1)],
    "full_table": [(_FULL, 1), (_FULL, 1), (_FULL, 1)],
    "none_runs": [None, (40, 0), None],
    "ragged": [None, (_FULL - 1, 0), (0, 1), (15, 1), (16, 1), (_FULL, 1),
               None],
}


def _assert_wave_rows(out, ref, valid, tol, err_msg=""):
    """The rows of the slots that run equal the reference's; the others
    are finite (the wave ignores them)."""
    out, run = np.asarray(out, np.float32), np.asarray(valid) > 0
    np.testing.assert_allclose(
        out[run], np.asarray(ref, np.float32)[run], atol=tol, rtol=tol,
        err_msg=err_msg,
    )
    assert np.isfinite(out).all(), err_msg


@pytest.mark.parametrize("wave", list(_WAVES))
@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4), (6, 2)])
def test_paged_decode_pallas_matches_xla_on_cpu_interpret(hq, hkv, wave):
    """Fused-kernel vs XLA-gather parity on CPU-interpretable shapes
    (GQA g=2, MHA g=1 and g=3 — the kernel picks each kv head's lane
    slice itself) over ragged waves: the running slots' outputs allclose
    at every legal block_kv — part of a page, one page, several — a slot
    that does not run gives a finite row, and the scattered pool is
    bitwise identical (the scatter is shared)."""
    from rocket_tpu.ops.paged_attention import paged_attention

    ops = _paged_operands(hq=hq, hkv=hkv, wave=_WAVES[wave])
    valid = ops[-1]
    ref, kx, vx = paged_attention(*ops, impl="xla")
    for block_kv in (8, 16, 64):
        out, kp, vp = paged_attention(
            *ops, impl="pallas", block_kv=block_kv, interpret=True
        )
        _assert_wave_rows(out, ref, valid, 1e-5, f"block_kv={block_kv}")
        np.testing.assert_array_equal(np.asarray(kp), np.asarray(kx))
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(vx))
    with pytest.raises(ValueError, match="block_kv"):
        paged_attention(*ops, impl="pallas", block_kv=12, interpret=True)
    with pytest.raises(ValueError, match="block_kv"):
        paged_attention(*ops, impl="pallas", block_kv=24, interpret=True)
    with pytest.raises(ValueError, match="impl"):
        paged_attention(*ops, impl="mosaic")


def test_paged_decode_cpu_default_is_xla_bitwise():
    """The CPU fallback: with no explicit impl (and no table entry) the
    dispatch must route to the XLA path and be BITWISE identical to it
    — an untuned CPU checkout behaves exactly like the pre-kernel code."""
    from rocket_tpu.ops.paged_attention import paged_attention

    ops = _paged_operands()
    ref, kx, vx = paged_attention(*ops, impl="xla")
    out, kp, vp = paged_attention(*ops)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(kx))
    # Unsupported page geometry (block_len % sublane != 0): an unpinned
    # call takes the XLA path, a PINNED kernel raises — it never
    # silently becomes the other path.
    small = _paged_operands(bl=4, mb=2)
    a, _, _ = paged_attention(*small, interpret=True)
    b, _, _ = paged_attention(*small, impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="cannot run here"):
        paged_attention(*small, impl="pallas", interpret=True)


def test_paged_decode_supported_gate():
    from rocket_tpu.ops.paged_attention import (
        _default_block_kv,
        paged_decode_supported,
    )

    assert paged_decode_supported(16, 64, 4, lanes=128)   # f32, one sublane tile
    assert paged_decode_supported(16, 64, 2, lanes=1280)  # bf16 at 16 rows
    assert not paged_decode_supported(8, 64, 2, lanes=128)   # bf16 needs 16 rows
    assert not paged_decode_supported(4, 64, 4, lanes=128)   # sub-sublane page
    assert not paged_decode_supported(16, 12, 4, lanes=384)  # D % 8
    assert not paged_decode_supported(16, 64, 4, lanes=192)  # HBM rows: 128 lanes
    assert not paged_decode_supported(16, 8, 4, lanes=24)
    # Nobody pinned a tile: up to 512 rows of context a step, as whole
    # pages or part of one, fewer where both buffers of a wide row would
    # overrun the VMEM budget.
    assert _default_block_kv(16) == 512
    assert _default_block_kv(1024) == 512
    assert _default_block_kv(48) == 16              # 512 ... 32 fit no page of 48
    assert _default_block_kv(16, 2, row_lanes=2 * 1280) == 128   # chat
    assert _default_block_kv(64, 2, row_lanes=640) == 512        # docqa
    assert _default_block_kv(16, 2, row_lanes=2 * 768) == 256    # GPT-2 124M
    assert _default_block_kv(16, 2, row_lanes=2 * 65536) == 16


# ---------------------------------------------------------------------------
# The shared sampling core / generate() satellite
# ---------------------------------------------------------------------------

def test_generate_per_sequence_limits_and_eos(tiny_lm):
    """generate() accepts per-sequence max_new_tokens / eos_token_id as
    runtime vectors: rows freeze at their own limits while the batch runs
    to the longest, and the scalar path is unchanged."""
    model, variables = tiny_lm
    prompt = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    base = np.asarray(generate(model, variables, prompt, 6, temperature=0))
    per = np.asarray(generate(
        model, variables, prompt, np.asarray([2, 6]), temperature=0,
    ))
    assert per.shape == (2, 9)
    # Row 0: its 2 tokens match the scalar run, then 0-fill (no eos).
    np.testing.assert_array_equal(per[0, 3:5], base[0, 3:5])
    assert (per[0, 5:] == 0).all()
    # Row 1 is untouched by row 0's early freeze.
    np.testing.assert_array_equal(per[1], base[1])
    # Per-sequence eos: freeze row 0 on its first generated token.
    eos_vec = np.asarray([int(base[0, 3]), -1], np.int32)
    with_eos = np.asarray(generate(
        model, variables, prompt, 6, temperature=0, eos_token_id=eos_vec,
    ))
    assert (with_eos[0, 3:] == int(base[0, 3])).all()
    np.testing.assert_array_equal(with_eos[1], base[1])


def test_sampling_core_array_scalar_parity():
    """Per-row arrays with uniform values must sample exactly like the
    scalar path modulo the per-row key derivation (greedy: identical)."""
    from rocket_tpu.models.sampling import freeze_after_eos, sample_tokens

    logits = jax.random.normal(jax.random.key(0), (4, 32))
    key = jax.random.key(7)
    greedy_scalar = sample_tokens(logits, key, 3, 0.0, None, None)
    greedy_rows = sample_tokens(
        logits, key, np.full((4,), 3, np.int32),
        np.zeros((4,), np.float32), np.zeros((4,), np.int32),
        np.ones((4,), np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(greedy_scalar), np.asarray(greedy_rows)
    )
    # top-k filter parity (deterministic part): k=1 forces the argmax.
    top1 = sample_tokens(
        logits, key, np.full((4,), 3, np.int32),
        np.ones((4,), np.float32), np.ones((4,), np.int32),
        np.ones((4,), np.float32),
    )
    np.testing.assert_array_equal(np.asarray(top1), np.asarray(greedy_scalar))
    # freeze_after_eos array form: -1 disables, fill is 0 once done.
    nxt = jnp.asarray([7, 7, 7], jnp.int32)
    done = jnp.asarray([True, True, False])
    eos = np.asarray([5, -1, 5], np.int32)
    out, done2 = freeze_after_eos(nxt, done, eos)
    np.testing.assert_array_equal(np.asarray(out), [5, 0, 7])
    np.testing.assert_array_equal(np.asarray(done2), [True, True, False])


# ---------------------------------------------------------------------------
# Sampling core: a call pays for what its rows ask for (ISSUE 34)
# ---------------------------------------------------------------------------

def _sample_rows_frozen(logits, key, salt, temperature, top_k, top_p):
    """The per-row path of ``sample_tokens`` as it stood before ISSUE 34,
    kept as the plain reference: a vocabulary sort for ``top_k``, another
    for ``top_p``, a draw for every row, and greedy rows taken last."""
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    k = jnp.asarray(top_k, jnp.int32)
    ranked = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        ranked, (jnp.clip(k, 1, vocab) - 1)[..., None], axis=-1
    )
    logits = jnp.where((k[..., None] > 0) & (logits < kth), -jnp.inf, logits)
    t = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.where(t > 0, t, 1.0)[..., None]
    ranked = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(ranked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    p = jnp.asarray(top_p, jnp.float32)[..., None]
    keep = cum - probs < p
    cutoff = jnp.min(jnp.where(keep, ranked, jnp.inf), axis=-1, keepdims=True)
    cutoff = jnp.where(p < 1.0, cutoff, -jnp.inf)
    scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.asarray(salt)
    )
    sampled = jax.vmap(
        lambda k_row, l_row: jax.random.categorical(k_row, l_row)
    )(keys, scaled)
    return jnp.where(t > 0, sampled, greedy)


_ROWS, _VOCAB = 6, 96


def _knobs(temp, top_k=0, top_p=1.0, run=True):
    """Per-row knob arrays as the scheduler keeps them (a scalar fills)."""
    return (
        np.broadcast_to(np.asarray(temp, np.float32), (_ROWS,)).copy(),
        np.broadcast_to(np.asarray(top_k, np.int32), (_ROWS,)).copy(),
        np.broadcast_to(np.asarray(top_p, np.float32), (_ROWS,)).copy(),
        np.broadcast_to(np.asarray(run, bool), (_ROWS,)).copy(),
    )


#: name -> (knobs, the branch the call must take, logits with ties?)
_SAMPLING_GRID = {
    "all_greedy": (_knobs(0.0), "argmax", False),
    "greedy_rows_carry_filters": (
        _knobs(0.0, [0, 5, 0, 9, 0, 1], [1.0, 0.5, 0.3, 1.0, 1.0, 0.9]),
        "argmax", False),
    "all_sampled_no_filter": (
        _knobs([0.7, 1.0, 1.3, 0.2, 2.0, 0.9]), "sample", False),
    "top_k_only": (_knobs(0.8, [3, 1, 10, 40, 2, 7]), "filter", False),
    "top_p_only": (
        _knobs(0.9, 0, [0.9, 0.5, 0.3, 0.95, 0.7, 0.1]), "filter", False),
    "top_k_and_top_p": (
        _knobs([0.7, 1.1, 0.9, 1.5, 0.6, 1.0], [5, 20, 3, 50, 8, 2],
               [0.9, 0.8, 0.5, 0.95, 0.3, 0.99]), "filter", False),
    "mixed_rows_of_each_kind": (
        _knobs([0.0, 0.8, 0.8, 0.0, 1.2, 0.9], [0, 0, 4, 7, 0, 6],
               [1.0, 1.0, 1.0, 0.4, 0.6, 0.8]), "filter", False),
    "greedy_beside_plain_sampled": (
        _knobs([0.0, 0.8, 0.0, 1.1, 0.0, 0.0]), "sample", False),
    "idle_row_carries_sampling_knobs": (
        _knobs([0.0, 0.8, 0.0, 0.0, 0.9, 0.0], [0, 5, 0, 0, 0, 0],
               [1.0, 0.9, 1.0, 1.0, 1.0, 1.0],
               [True, False, True, True, False, True]), "argmax", False),
    "idle_row_filters_running_row_samples": (
        _knobs([0.0, 0.8, 0.7, 0.0, 0.9, 0.0], [0, 5, 0, 0, 0, 0],
               [1.0, 0.9, 1.0, 1.0, 1.0, 1.0],
               [True, False, True, True, False, True]), "sample", False),
    "ties_at_the_kth_logit": (
        _knobs([0.8, 1.0, 0.7, 0.0, 1.2, 0.9], [3, 5, 2, 4, 8, 1],
               [1.0, 0.9, 1.0, 1.0, 0.5, 1.0]), "filter", True),
    "top_k_at_and_past_the_vocabulary": (
        _knobs(0.9, [_VOCAB, _VOCAB + 1, 10 * _VOCAB, _VOCAB - 1, 2**30, 3]),
        "filter", False),
    "top_p_one_beside_a_third": (
        _knobs(1.0, 0, [1.0, 0.3, 1.0, 0.3, 1.0, 0.3]), "filter", False),
}


def _grid_logits(ties):
    logits = 3.0 * jax.random.normal(jax.random.key(34), (_ROWS, _VOCAB))
    # Ties: a few distinct values a row, so the k-th value repeats.
    return jnp.round(logits) if ties else logits


@pytest.mark.parametrize("case", sorted(_SAMPLING_GRID))
def test_sampling_core_row_path_returns_the_frozen_tokens(case):
    """Whatever its rows ask for, the per-row path returns exactly the
    tokens of the function as it stood (greedy rows the argmax, sampled
    rows the same draw from the same per-row key, filtered rows the same
    cutoffs), over several keys and salts; a row outside ``run`` is a
    greedy row."""
    from rocket_tpu.models.sampling import sample_tokens

    (temp, top_k, top_p, run), _, ties = _SAMPLING_GRID[case]
    logits = _grid_logits(ties)
    new = jax.jit(sample_tokens)
    old = jax.jit(_sample_rows_frozen)
    calm = np.where(run, temp, np.float32(0.0))
    for seed in (0, 7, 2**31 - 1):
        for salt0 in (0, 1000003):
            salts = np.arange(salt0, salt0 + 31 * _ROWS, 31, dtype=np.int32)
            key = jax.random.key(seed)
            got = np.asarray(
                new(logits, key, salts, temp, top_k, top_p, run=run))
            want = np.asarray(old(logits, key, salts, calm, top_k, top_p))
            np.testing.assert_array_equal(got, want)
            if run.all():  # without the mask: the same call
                np.testing.assert_array_equal(np.asarray(
                    new(logits, key, salts, temp, top_k, top_p)), want)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(got[calm <= 0], greedy[calm <= 0])


@pytest.mark.parametrize("case", sorted(_SAMPLING_GRID))
def test_sample_branch_agrees_on_host_and_device(case):
    """ONE predicate: numpy arrays (the scheduler's counter) and jax arrays
    under ``jit`` (the device's ``switch``) name the same branch."""
    from rocket_tpu.models.sampling import SAMPLE_BRANCHES, sample_branch

    (temp, top_k, top_p, run), branch, _ = _SAMPLING_GRID[case]
    host = sample_branch(temp, top_k, top_p, run)
    assert isinstance(host, np.integer)  # no device touched
    device = jax.jit(sample_branch)(temp, top_k, top_p, run)
    assert SAMPLE_BRANCHES[int(host)] == SAMPLE_BRANCHES[int(device)] == branch
    # The device neutralises the idle rows first and passes no mask.
    calm = np.where(run, temp, np.float32(0.0))
    assert int(jax.jit(sample_branch)(calm, top_k, top_p)) == int(host)


def _primitives(jaxpr, inside=()):
    """``(primitive name, names of the enclosing primitives)`` for every
    equation of ``jaxpr`` and of the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub, inside + (eqn.primitive.name,))


def test_sampling_core_row_path_sorts_once_inside_a_branch():
    """The per-row path holds ONE vocabulary sort, inside a branch of the
    ``switch`` (a ``cond`` in the jaxpr): a call whose rows do not filter
    never runs it. The scalar path of ``generate()`` keeps its own."""
    from rocket_tpu.models.sampling import sample_tokens

    temp, top_k, top_p, run = _knobs(0.8, 5, 0.9)
    salts = np.arange(_ROWS, dtype=np.int32)
    logits, key = _grid_logits(False), jax.random.key(0)
    for kwargs in ({}, {"run": run}):
        jaxpr = jax.make_jaxpr(
            lambda *a: sample_tokens(*a, **kwargs)
        )(logits, key, salts, temp, top_k, top_p).jaxpr
        sorts = [inside for name, inside in _primitives(jaxpr)
                 if name == "sort"]
        assert len(sorts) == 1 and "cond" in sorts[0], sorts
    # A scalar among per-row arrays is broadcast: still the one sort.
    jaxpr = jax.make_jaxpr(
        lambda l, k, s, t: sample_tokens(l, k, s, t, 5, 0.9)
    )(logits, key, salts, temp).jaxpr
    sorts = [inside for name, inside in _primitives(jaxpr) if name == "sort"]
    assert len(sorts) == 1 and "cond" in sorts[0], sorts
    # Scalars all: the static path, no conditional.
    jaxpr = jax.make_jaxpr(
        lambda l, k: sample_tokens(l, k, 3, 0.8, None, 0.9))(logits, key).jaxpr
    names = [name for name, _ in _primitives(jaxpr)]
    assert names.count("sort") == 1 and "cond" not in names


def _sample_records(engine, submit):
    """The ``sample=`` ids of the ``serve/decode_pages`` records that
    ``engine`` writes while ``submit(engine)``'s requests drain."""
    from rocket_tpu.obs import spans

    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        submit(engine)
        engine.drain()
    finally:
        spans.uninstall(recorder)
    return [e.ids["sample"] for e in recorder.events()
            if e.name == "serve/decode_pages"]


def test_scheduler_records_the_sampling_branch_of_each_wave(tiny_lm):
    """``serve/decode_pages`` carries ``sample=``: ``argmax`` while the
    RUNNING slots are greedy (a sampling request still prefilling does not
    count), ``filter`` once a running request has ``top_p`` 0.9, ``sample``
    for a temperature alone."""
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=48),
    )
    short = np.arange(1, 4, dtype=np.int32)

    def greedy(engine):
        for _ in range(3):
            engine.submit(short, max_new_tokens=4, temperature=0.0)

    assert set(_sample_records(engine, greedy)) == {"argmax"}

    def one_filters(engine):
        greedy(engine)
        # Five chunks of prefill: the greedy slots decode meanwhile.
        engine.submit(np.arange(1, 19, dtype=np.int32), max_new_tokens=3,
                      temperature=0.8, top_p=0.9)

    records = _sample_records(engine, one_filters)
    assert records[0] == "argmax" and records[-1] == "filter"
    assert set(records) == {"argmax", "filter"}
    assert records == sorted(records)  # it filters from its first wave on

    def one_samples(engine):
        engine.submit(short, max_new_tokens=3, temperature=0.8)

    assert set(_sample_records(engine, one_samples)) == {"sample"}
    assert engine.engine.decode_traces == 1  # one program, every branch


def test_scanned_waves_choose_their_sampling_wave_by_wave(tiny_lm):
    """The branch sits inside the scan's wave and reads the carried run
    mask: with one sampled request that finishes mid-scan, every request
    (the sampled one too: its salt is its seed and length) streams the
    same tokens at 1 and at 2 waves a dispatch."""
    model, variables = tiny_lm

    def run(k):
        engine = ServeEngine(
            model, variables["params"],
            ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                        max_model_len=48, decode_waves_per_dispatch=k),
        )
        rng = np.random.default_rng(34)
        rids = [
            engine.submit(rng.integers(0, 64, size=n).astype(np.int32),
                          max_new_tokens=new, temperature=0.0)
            for n, new in ((3, 8), (6, 5), (2, 10))
        ]
        # Three tokens: the second dispatch of two waves ends it mid-scan.
        rids.append(engine.submit(
            np.asarray([4, 5, 6], np.int32), max_new_tokens=3,
            temperature=0.9, top_k=8, top_p=0.9))
        engine.drain()
        assert engine.engine.decode_traces == 1
        return [engine.result(rid).tokens for rid in rids]

    one, two = run(1), run(2)
    assert [len(t) for t in one] == [8, 5, 10, 3]
    assert one == two


def test_reset_metrics_windows_registry_histograms(tiny_lm):
    """reset_metrics() windows the registry-side serve histograms too:
    the /metrics endpoint and telemetry.json percentiles must describe
    the same steady-state window the report does, while the lifetime
    trace-count gauges (the no-retrace proof) survive the reset."""
    from rocket_tpu.obs.telemetry import Telemetry

    model, variables = tiny_lm
    telemetry = Telemetry(enabled=True)
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=32),
        telemetry=telemetry,
    )
    for seed in range(4):
        prompt = np.arange(1, 5, dtype=np.int32) + seed
        engine.submit(prompt, max_new_tokens=4, temperature=0.0)
    engine.drain()

    hists = telemetry.registry.snapshot()["histograms"]
    assert hists["serve/ttft_s"]["count"] == 4
    assert hists["serve/itl_s"]["count"] > 0

    engine.reset_metrics()
    snap = telemetry.registry.snapshot()
    assert snap["histograms"]["serve/ttft_s"]["count"] == 0
    assert snap["histograms"]["serve/ttft_s"]["buckets"] == {}
    assert snap["histograms"]["serve/itl_s"]["count"] == 0
    # Lifetime gauges are NOT windowed: still the compiled-once proof.
    assert snap["gauges"]["serve/decode_traces"] == 1
    assert snap["gauges"]["serve/prefill_traces"] == 1

    # Steady state re-accumulates into the fresh window.
    engine.submit(np.asarray([3, 1, 2], np.int32), max_new_tokens=3,
                  temperature=0.0)
    engine.drain()
    hists = telemetry.registry.snapshot()["histograms"]
    assert hists["serve/ttft_s"]["count"] == 1


def test_scheduler_records_decode_pages_per_dispatched_wave(tiny_lm):
    """The counter ``serve/decode_pages``: one zero-length record per
    dispatched wave while spans are on, with ``live`` = the pages the
    wave's RUNNING slots hold (``lengths // block_len + 1`` each: a slot
    still prefilling holds pages and is not counted) and ``table`` =
    ``max_slots x max_blocks_per_seq``; off, nothing is recorded."""
    from rocket_tpu.obs import spans

    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=32),
    )
    sched = engine.scheduler
    want, dispatch = [], engine.engine.decode_dispatch

    def counted(block_table, lengths, last_tok, run_mask, *rest):
        want.append(int(sum(n // 4 + 1 for n in lengths[run_mask])))
        return dispatch(block_table, lengths, last_tok, run_mask, *rest)

    engine.engine.decode_dispatch = counted
    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        for n in (3, 9, 14):        # 14 = four chunks: prefilling while others run
            engine.submit(np.arange(1, 1 + n, dtype=np.int32),
                          max_new_tokens=5, temperature=0.0)
        engine.drain()
    finally:
        spans.uninstall(recorder)
    records = [e for e in recorder.events() if e.name == "serve/decode_pages"]
    assert len(records) == len(want) == engine.engine.decode_dispatches > 0
    assert [e.ids["live"] for e in records] == want
    assert {e.ids["table"] for e in records} == {4 * 8}
    assert all(e.start <= e.end for e in records)
    # A wave with one running slot of 3..4 tokens holds one or two pages;
    # no wave holds the whole table.
    assert 1 <= min(want) <= 2 and max(want) < 4 * 8
    assert len({e.ids["tick"] for e in records}) == len(records)

    before = len(spans.recorded())
    engine.submit(np.arange(1, 4, dtype=np.int32), max_new_tokens=2,
                  temperature=0.0)
    engine.drain()
    assert not [e for e in spans.recorded()[before:]
                if e.name == "serve/decode_pages"]
