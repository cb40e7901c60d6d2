"""Two decode dispatches in flight: the scheduler enqueues dispatch N+1
before it fetches dispatch N, and the decode program takes every running
slot's ``(lengths, last token, run mask)`` from the carry the dispatch
before left on the device (``serve/engine.py``, ``serve/scheduler.py``).

What must hold: the order itself; the same tokens as ``generate()`` and as
the old order (harvest, then dispatch from exact host mirrors); a slot that
finishes in flight is frozen by the device although the host asked for it;
the block table is grown one dispatch ahead; an empty pool drains before it
evicts; nothing retraces. Tiny widths, CPU.
"""

import numpy as np
import pytest

import jax

from rocket_tpu.models.transformer import TransformerConfig, TransformerLM, generate
from rocket_tpu.obs import spans
from rocket_tpu.serve import ServeConfig, ServeEngine


@pytest.fixture(scope="module")
def tiny_lm():
    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(0))
    return model, variables


def _engine(tiny_lm, **over):
    model, variables = tiny_lm
    sc = dict(max_slots=4, block_len=4, prefill_chunk=4, max_model_len=48)
    sc.update(over)
    return ServeEngine(model, variables["params"], ServeConfig(**sc))


def _greedy_reference(tiny_lm, prompt, max_new):
    model, variables = tiny_lm
    full = generate(model, variables, prompt[None, :], max_new, temperature=0)
    return np.asarray(full)[0, len(prompt):].tolist()


def _log_calls(engine):
    """Wrap the SlotEngine's two drivers. The log holds, in call order,
    ``("dispatch", seq, inputs)`` with copies of what the scheduler handed
    over and ``("harvest", seq, (tokens, done, emitted))``."""
    log = []
    slot_engine = engine.engine
    dispatch, harvest = slot_engine.decode_dispatch, slot_engine.harvest

    def logged_dispatch(block_table, lengths, last_tok, run_mask, *rest):
        inputs = dict(table=block_table.copy(), lengths=lengths.copy(),
                      run=run_mask.copy(), fresh=rest[-1].copy())
        handle = dispatch(block_table, lengths, last_tok, run_mask, *rest)
        log.append(("dispatch", handle.seq, inputs))
        return handle

    def logged_harvest(handle):
        out = harvest(handle)
        log.append(("harvest", handle.seq, out))
        return out

    slot_engine.decode_dispatch = logged_dispatch
    slot_engine.harvest = logged_harvest
    return log


def _old_order(engine):
    """The tick order before two dispatches were in flight, on the same
    code: every tick harvests before it grows and dispatches, and every
    slot's ``lengths`` / ``last_tok`` come from the (then exact) host
    mirrors, none from the device's carry."""
    engine.scheduler._pool_short = lambda: True
    dispatch = engine.engine.decode_dispatch
    engine.engine.decode_dispatch = lambda *args: dispatch(*args[:-1])
    return engine


def _workload(n, seed, *, sampled):
    rng = np.random.default_rng(seed)
    requests = []
    for i in range(n):
        prompt = rng.integers(0, 64, size=int(rng.integers(1, 15))).astype(np.int32)
        knobs = dict(max_new_tokens=int(rng.integers(1, 10)), temperature=0.0)
        if sampled:
            knobs["temperature"] = float(rng.uniform(0.5, 1.2))
            if i % 3 == 0:
                knobs["top_k"] = int(rng.integers(2, 9))
            if i % 3 == 1:
                knobs["top_p"] = float(rng.uniform(0.5, 0.95))
        requests.append((prompt, knobs))
    return requests


def _serve(engine, requests):
    rids = [engine.submit(prompt, **knobs) for prompt, knobs in requests]
    engine.drain()
    return [engine.result(rid).tokens for rid in rids]


# -- (a) the order ------------------------------------------------------------

def test_the_next_dispatch_is_enqueued_before_the_last_is_fetched(tiny_lm, monkeypatch):
    engine = _engine(tiny_lm)
    log = _log_calls(engine)
    fetched, device_get = [], jax.device_get

    def counted_get(tree):
        fetched.extend(jax.tree.leaves(tree))
        return device_get(tree)

    monkeypatch.setattr(jax, "device_get", counted_get)
    requests = [(np.arange(1, 4 + i, dtype=np.int32),
                 dict(max_new_tokens=12 + i, temperature=0.0)) for i in range(3)]
    _serve(engine, requests)
    # The carry is the next dispatch's input and nobody's fetch: three
    # arrays a dispatch's harvest brings (tokens, done, emitted), no more.
    assert len(fetched) == 3 * engine.engine.device_gets
    assert all(leaf.ndim == 2 for leaf in fetched)
    assert all(isinstance(leaf, jax.Array) for leaf in engine.engine.carry)
    at = {(kind, seq): i for i, (kind, seq, _) in enumerate(log)}
    dispatched = max(seq for kind, seq, _ in log if kind == "dispatch")
    assert dispatched >= 12
    # Steady decoding: N+1 is in the device's queue when N is fetched.
    for seq in range(dispatched):
        assert at[("dispatch", seq + 1)] < at[("harvest", seq)], seq
    outstanding, most = 0, 0
    for kind, _, _ in log:
        outstanding += 1 if kind == "dispatch" else -1
        assert 0 <= outstanding <= 2
        most = max(most, outstanding)
    assert most == 2 and outstanding == 0
    assert engine.engine.device_gets == engine.engine.decode_dispatches
    assert engine.scheduler.idle and engine.scheduler.pending is None


# -- (b) the same tokens ------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_tokens_are_the_old_orders_and_generates(tiny_lm, k, sampled):
    """24 requests over 4 slots: admissions into freed slots, prompts of
    several chunks, refills. Greedy or sampled (each request's seed is its
    id), the tokens are those of the old order; greedy ones are
    ``generate()``'s too."""
    requests = _workload(24, seed=36 + k, sampled=sampled)
    engine = _engine(tiny_lm, decode_waves_per_dispatch=k)
    got = _serve(engine, requests)
    old = _serve(_old_order(_engine(tiny_lm, decode_waves_per_dispatch=k)), requests)
    assert got == old
    assert all(len(tokens) == knobs["max_new_tokens"]
               for tokens, (_, knobs) in zip(got, requests))
    if not sampled:
        for tokens, (prompt, knobs) in zip(got, requests):
            assert tokens == _greedy_reference(
                tiny_lm, prompt, knobs["max_new_tokens"])
    assert engine.engine.decode_traces == engine.engine.prefill_traces == 1
    assert engine.engine.device_gets == engine.engine.decode_dispatches


# -- (c) a slot that finishes in flight ---------------------------------------

def test_an_eos_in_flight_freezes_the_slot_in_the_next_dispatch(tiny_lm):
    """The slot emits EOS in dispatch N. N+1 was enqueued before the host
    fetched N, with the slot in its run mask: the device's carried mask
    froze it there, it emitted nothing, and the request ends at the EOS."""
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    ref = _greedy_reference(tiny_lm, prompt, 9)
    eos = ref[3]
    first = ref.index(eos)
    engine = _engine(tiny_lm, max_slots=2)
    log = _log_calls(engine)
    got = _serve(engine, [(prompt, dict(max_new_tokens=9, temperature=0.0,
                                        eos_token_id=eos))])[0]
    assert got == ref[:first + 1] and got[-1] == eos and len(got) < 9
    harvests = {seq: out for kind, seq, out in log if kind == "harvest"}
    dispatches = {seq: inputs for kind, seq, inputs in log if kind == "dispatch"}
    at = {(kind, seq): i for i, (kind, seq, _) in enumerate(log)}
    (ended,) = [seq for seq, (_, done, _) in harvests.items() if done.any()]
    slot = int(np.nonzero(harvests[ended][1][0])[0][0])
    after = dispatches[ended + 1]
    assert at[("dispatch", ended + 1)] < at[("harvest", ended)]
    assert after["run"][slot] and not after["fresh"][slot]
    assert not harvests[ended + 1][2].any()        # nothing emitted
    assert engine.scheduler.active_slots == 0
    assert engine.scheduler.allocator.free_fraction == 1.0


# -- (d) the table is grown one dispatch ahead --------------------------------

@pytest.mark.parametrize("k", [1, 2])
def test_the_table_covers_the_rows_of_the_dispatch_in_flight_and_the_next(tiny_lm, k):
    """Blocks of 4: every few waves a slot's token crosses into a block
    the host had to allocate while its mirror of ``lengths`` was still one
    dispatch behind. Replaying the log with the device's own lengths: the
    row of every emitted token lies in an allocated block of the table its
    dispatch read (block 0 is the trash block), and the tokens are the
    reference's."""
    engine = _engine(tiny_lm, decode_waves_per_dispatch=k)
    log = _log_calls(engine)
    requests = _workload(10, seed=4, sampled=False)
    requests = [(p, dict(kn, max_new_tokens=kn["max_new_tokens"] + 12))
                for p, kn in requests]
    got = _serve(engine, requests)
    for tokens, (prompt, knobs) in zip(got, requests):
        assert tokens == _greedy_reference(tiny_lm, prompt, knobs["max_new_tokens"])
    harvests = {seq: out for kind, seq, out in log if kind == "harvest"}
    held = np.zeros((4,), np.int32)
    crossed = 0
    for kind, seq, inputs in log:
        if kind != "dispatch":
            continue
        lengths = np.where(inputs["fresh"], inputs["lengths"], held)
        stale = ~inputs["fresh"] & inputs["run"] & (inputs["lengths"] != held)
        for wave in harvests[seq][2]:
            for slot in np.nonzero(wave)[0]:
                block = inputs["table"][slot, lengths[slot] // 4]
                assert block != 0, (seq, slot, lengths[slot])
                crossed += bool(stale[slot]) and lengths[slot] % 4 == 0
            lengths = lengths + wave
        held = lengths
    # The case in question did occur: a first row of a block written by a
    # slot whose host mirror was behind when the table was handed over.
    assert crossed >= 5


# -- (e) an empty pool drains before it evicts --------------------------------

def test_an_empty_pool_drains_before_it_evicts(tiny_lm):
    engine = _engine(tiny_lm, max_model_len=32, num_blocks=9)   # 8 allocatable
    scheduler, slot_engine = engine.scheduler, engine.engine
    in_flight = []
    evict = scheduler._evict

    def watched(slot):
        in_flight.append(slot_engine.decode_dispatches - slot_engine.device_gets)
        evict(slot)

    scheduler._evict = watched
    rng = np.random.default_rng(3)
    requests = [
        (rng.integers(0, 64, size=int(rng.integers(4, 12))).astype(np.int32),
         dict(max_new_tokens=int(rng.integers(8, 16)), temperature=0.0))
        for _ in range(8)
    ]
    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        got = _serve(engine, requests)
    finally:
        spans.uninstall(recorder)
    assert in_flight and not any(in_flight)       # nothing in flight to lose
    assert engine.report()["requests"]["preemptions"] == len(in_flight)
    for tokens, (prompt, knobs) in zip(got, requests):
        assert tokens == _greedy_reference(tiny_lm, prompt, knobs["max_new_tokens"])
    grows = [ev.ids for ev in recorder.events() if ev.name == "serve/grow"]
    assert any(ids["drained"] for ids in grows)
    assert any(not ids["drained"] for ids in grows)
    assert engine.scheduler.allocator.free_fraction == 1.0


# -- (g) nothing retraces -----------------------------------------------------

def test_fresh_takes_many_values_and_nothing_retraces(tiny_lm):
    engine = _engine(tiny_lm)
    log = _log_calls(engine)
    got = _serve(engine, _workload(60, seed=9, sampled=False))
    assert len(got) == 60 and engine.report()["requests"]["completed"] == 60
    patterns = {tuple(inputs["fresh"]) for kind, _, inputs in log if kind == "dispatch"}
    assert len(patterns) >= 8, patterns
    assert engine.engine.decode_traces == 1
    assert engine.engine.prefill_traces == 1
