"""Direct coverage for core/scheduler.py: TokenBucket refill/burst
semantics under thread contention, and ContinuousBatcher admission
ordering (FIFO pending queue, slot reuse)."""
import threading
import time

import numpy as np
import pytest

from repro.core.scheduler import ContinuousBatcher, TokenBucket


# ---------------------------------------------------------------------------
# TokenBucket
# ---------------------------------------------------------------------------
def test_burst_then_empty():
    tb = TokenBucket(rate=0.0, burst=5.0)
    grants = [tb.try_take() for _ in range(8)]
    assert grants == [True] * 5 + [False] * 3


def test_refill_caps_at_burst():
    tb = TokenBucket(rate=1000.0, burst=3.0)
    for _ in range(3):
        assert tb.try_take()
    time.sleep(0.05)                         # >> burst/rate: fully refilled
    grants = sum(tb.try_take() for _ in range(10))
    # refill is capped at burst: after ANY idle period at most `burst`
    # tokens are available immediately (a trickle may add 1 during the
    # take loop itself)
    assert 3 <= grants <= 4


def test_fractional_take_and_refill_rate():
    tb = TokenBucket(rate=10.0, burst=1.0)
    assert tb.try_take(1.0)
    assert not tb.try_take(1.0)
    time.sleep(0.25)                  # ~2.5 tokens accrued, capped at 1
    assert tb.try_take(1.0)
    assert not tb.try_take(1.0)


def test_contention_grants_exactly_burst_with_no_refill():
    # rate=0: the bucket can never refill, so across ANY interleaving of
    # 8 hammering threads exactly `burst` takes may succeed — lost
    # updates would grant more, lock starvation fewer
    tb = TokenBucket(rate=0.0, burst=100.0)
    granted = []
    barrier = threading.Barrier(8)

    def work():
        barrier.wait(timeout=5.0)
        mine = 0
        for _ in range(200):
            if tb.try_take():
                mine += 1
        granted.append(mine)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
    assert sum(granted) == 100


def test_contention_with_refill_never_exceeds_budget():
    # with refill, total grants over a window are bounded by
    # burst + rate * elapsed (plus one token of measurement slack)
    tb = TokenBucket(rate=200.0, burst=10.0)
    granted = []
    stop = time.monotonic() + 0.25

    def work():
        mine = 0
        while time.monotonic() < stop:
            if tb.try_take():
                mine += 1
        granted.append(mine)

    t0 = time.monotonic()
    threads = [threading.Thread(target=work) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
    elapsed = time.monotonic() - t0
    assert sum(granted) <= 10 + 200.0 * elapsed + 1


# ---------------------------------------------------------------------------
# ContinuousBatcher
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm_runtime():
    from conftest import bf16_params

    from repro.configs import get_config
    from repro.core import HydraRuntime, LMSpec
    from repro.models.programs import ModelProgram

    cfg = get_config("qwen2.5-3b").reduced()
    params = bf16_params(ModelProgram(cfg))
    rt = HydraRuntime(memory_budget_bytes=2 << 30)
    rt.register_function("t0/lm", LMSpec(cfg=cfg, params=params,
                                         max_seq=64, slots=2),
                         tenant="t0")
    yield rt
    rt.shutdown()


def test_admission_is_fifo_and_bounded_by_slots(lm_runtime):
    b = ContinuousBatcher(lm_runtime, "t0/lm")
    try:
        futs = [b.submit(list(range(4)), max_new=3) for _ in range(3)]
        b.step()
        # 2 slots: the first two pending requests were admitted in
        # submission order; the third stays pending
        assert len(b.active) == 2
        assert len(b.pending) == 1
        admitted = {req.future for req in b.active.values()}
        assert admitted == {futs[0], futs[1]}
        assert b.pending[0].future is futs[2]
        # requests 0/1 finish first (equal max_new), freeing slots for 2
        b.run_until_done(max_steps=50)
        assert all(f.done() for f in futs)
        assert futs[2].result()  # admitted after a slot freed
        done_order = sorted(range(3), key=lambda i: len(futs[i].result()))
        assert all(len(f.result()) == 3 for f in futs), done_order
        assert not b.pending and not b.active
        assert sorted(b.free) == [0, 1]
    finally:
        b.close()


def test_slot_reuse_keeps_serving_after_drain(lm_runtime):
    b = ContinuousBatcher(lm_runtime, "t0/lm")
    try:
        first = [b.submit([1, 2, 3], max_new=2) for _ in range(2)]
        b.run_until_done(max_steps=50)
        assert all(len(f.result()) == 2 for f in first)
        # slots were returned: a second wave admits (and with max_new=2
        # completes — prefill + one decode) within a single step
        second = b.submit([4, 5], max_new=2)
        b.step()
        assert second.done()
        assert len(second.result()) == 2
        assert not b.pending and not b.active
    finally:
        b.close()


@pytest.mark.parametrize("n_requests,max_new", [(1, 2), (2, 4), (5, 3)])
def test_one_readback_per_program_call(lm_runtime, n_requests, max_new):
    # every prefill and every decode call costs one device-to-host
    # transfer, however many slots are active; 5 requests on 2 slots
    # admit in later steps too
    b = ContinuousBatcher(lm_runtime, "t0/lm")
    try:
        futs = [b.submit([7, 8, 9], max_new=max_new)
                for _ in range(n_requests)]
        b.step()
        admitted = len(b.active) + sum(f.done() for f in futs)
        assert b.readbacks == b.steps + admitted
        b.run_until_done(max_steps=50)
        assert b.readbacks == b.steps + n_requests
        outs = [f.result() for f in futs]
    finally:
        b.close()
    assert all(len(o) == max_new for o in outs)
    # plain Python ints, not device or numpy scalars
    assert all(type(t) is int for o in outs for t in o)
    assert len({tuple(o) for o in outs}) == 1   # same prompt, same tokens


# ---------------------------------------------------------------------------
# free slots hold cache length 0
# ---------------------------------------------------------------------------
def _lengths(b):
    return np.asarray(b.cache["length"]).tolist()


def _expected_lengths(b):
    """A live slot holds its prompt and every emitted token but the last;
    a free slot holds 0."""
    want = [0] * b.slots
    for slot, req in b.active.items():
        want[slot] = len(req.prompt) + len(req.emitted) - 1
    return want


def test_retired_slot_holds_length_zero(lm_runtime):
    b = ContinuousBatcher(lm_runtime, "t0/lm")
    try:
        short = b.submit([1, 2, 3, 4], max_new=3)
        long = b.submit([5, 6, 7], max_new=9)
        b.step()
        assert _lengths(b) == [5, 4]
        b.step()                     # the short request retires here
        assert short.done() and list(b.active) == [1]
        assert _lengths(b) == [0, 5]
        for n in range(6, 11):       # the free slot stays at 0
            b.step()
            assert _lengths(b) == [0, n]
        b.step()                     # the long request's ninth token
        assert long.done() and _lengths(b) == [0, 0]
    finally:
        b.close()


def test_free_slot_never_outgrows_cache(lm_runtime):
    # one request at a time on 2 slots: FIFO reuse leaves each slot free
    # for a whole request, over 200 decode calls in all; a free length
    # that kept growing would pass max_seq (64) within two requests
    b = ContinuousBatcher(lm_runtime, "t0/lm")
    max_seq = lm_runtime.registry.get("t0/lm").spec.max_seq
    try:
        for i in range(6):
            fut = b.submit([3 + i, 4, 5], max_new=40)
            while not fut.done():
                b.step()
                lengths = _lengths(b)
                assert max(lengths) <= max_seq, (b.steps, lengths)
                assert lengths == _expected_lengths(b), b.steps
            assert len(fut.result()) == 40
        assert b.steps == 6 * 39
    finally:
        b.close()


def test_live_tokens_match_every_slot_busy(lm_runtime):
    prompts = [[11, 12, 13, 14], [21, 22, 23]]
    b = ContinuousBatcher(lm_runtime, "t0/lm")
    try:
        busy = [b.submit(p, max_new=6) for p in prompts]
        b.run_until_done(max_steps=50)
        busy = [f.result() for f in busy]
        # each alone, beside a free slot; then the second joins late
        alone = []
        for p in prompts:
            alone.append(b.submit(p, max_new=6))
            b.run_until_done(max_steps=50)
        first = b.submit(prompts[0], max_new=6)
        b.step()
        b.step()
        late = b.submit(prompts[1], max_new=6)
        b.run_until_done(max_steps=50)
    finally:
        b.close()
    assert [f.result() for f in alone] == busy
    assert [first.result(), late.result()] == busy


# ---------------------------------------------------------------------------
# host phases on the profiler's clock
# ---------------------------------------------------------------------------
def _host_events(trace_dir):
    """(line index, name, start, end, args) of every ``hydra.`` event on
    the host plane of the profile written under ``trace_dir``."""
    import glob
    import os

    import jax
    [path] = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                       recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("hydra."):
                    out.append((i, e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[2])


def test_step_phases_nest_on_the_profiler_clock(lm_runtime, tmp_path):
    import gc

    import jax
    b = ContinuousBatcher(lm_runtime, "t0/lm")
    try:
        # a prompt length no other test compiles: the first admission
        # builds its prefill program
        futs = [b.submit([1, 2, 3, 4, 5], max_new=3) for _ in range(3)]
        jax.profiler.start_trace(str(tmp_path))
        try:
            b.run_until_done(max_steps=50)
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        assert all(len(f.result()) == 3 for f in futs)
    finally:
        b.close()
    events = _host_events(tmp_path)
    steps = [e for e in events if e[1] == "hydra.lm.step"]
    assert len(steps) == b.steps
    line = steps[0][0]
    admits_seen = 0
    for _, _, s0, s1, _ in steps:
        inside = [e for e in events if e[0] == line and s0 <= e[2]
                  and e[3] <= s1 and e[1] != "hydra.lm.step"]
        names = [e[1] for e in inside]
        admits = [e for e in inside if e[1] == "hydra.lm.admit"]
        admits_seen += len(admits)
        assert names.count("hydra.lm.decode") == 1
        assert names.count("hydra.lm.sync") == 1
        decode = next(e for e in inside if e[1] == "hydra.lm.decode")
        sync = next(e for e in inside if e[1] == "hydra.lm.sync")
        # admissions come first, then the dispatch, then the readback
        assert all(a[3] <= decode[2] for a in admits)
        assert decode[3] <= sync[2]
        assert decode[4]["active"] == sync[4]["tokens"]
        for a in admits:
            assert set(a[4]) == {"slot", "prompt_len", "waited_us",
                                 "compiled"}
            assert a[4]["prompt_len"] == 5 and a[4]["slot"] in (0, 1)
            assert a[4]["waited_us"] >= 0
    assert admits_seen == 3
    assert [e[4]["compiled"] for e in events
            if e[1] == "hydra.lm.admit"] == [1, 0, 0]
    # 2 slots, 3 requests of 3 tokens: the decode calls ran with the
    # slots in use at each step
    assert [e[4]["active"] for e in events
            if e[1] == "hydra.lm.decode"] == [2, 2, 1, 1]
    # positions attended: prompt 5 plus the tokens emitted, per slot
    assert [e[4]["kv_positions"] for e in events
            if e[1] == "hydra.lm.decode"] == [12, 14, 6, 7]
    gcs = [e for e in events if e[1] == "hydra.gc"]
    assert any(e[4]["generation"] == 2 for e in gcs)
