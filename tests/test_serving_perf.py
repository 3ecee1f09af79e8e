"""Serving performance layer (ROADMAP item 1): radix prefix cache,
speculative decoding, int8 batched decode, open-loop traffic.

The contract every test here enforces is the same one: the performance
layer may only SKIP work, never change tokens. Prefix reuse is bitwise
against cold prefill, speculative greedy is identical to stock decode,
the int8 engine matches ``decode.generate(quantize_cache=True)`` — and
when a reuse path faults (chaos site ``serve.prefix``), the fallback is
a cold prefill, not a wrong answer. Design: docs/design/serving_perf.md.
"""

import threading

import numpy as np
import pytest

from dlrover_tpu import chaos
from dlrover_tpu.observability.journal import JournalEvent
from dlrover_tpu.serving.engine import ToyEngine, build_tiny_engine
from dlrover_tpu.serving.prefix_cache import (
    SERVE_PREFIX_SITE,
    PrefixCachingEngine,
    RadixPrefixCache,
    maybe_wrap_prefix_cache,
)
from dlrover_tpu.serving.speculative import (
    SpeculativeDecoder,
    build_tiny_spec_pair,
)
from dlrover_tpu.serving.traffic import (
    OpenLoopGenerator,
    TrafficProfile,
    percentile,
)


@pytest.fixture(autouse=True)
def _reset_injector():
    yield
    chaos.reset_injector()


# -- trie insert / hit / evict algebra --------------------------------------


def test_lookup_is_block_quantized_and_strictly_inside_prompt():
    cache = RadixPrefixCache(max_bytes=10_000, block=4)
    prompt = list(range(12))
    cache.insert(prompt, "A", 100)
    # full re-ask: best match is the whole prompt, but the last token's
    # row must be computed → min(12, 11) → block-rounded to 8
    m, key, payload = cache.lookup(prompt)
    assert (m, payload) == (8, "A")
    cache.unpin(key)
    # 6 shared tokens → rounded down to one block
    m, key, payload = cache.lookup(prompt[:6] + [99, 98])
    assert (m, payload) == (4, "A")
    cache.unpin(key)
    # under one block of overlap is a miss
    assert cache.lookup(prompt[:3] + [99, 98, 97]) == (0, None, None)


def test_insert_skips_unusable_entries():
    cache = RadixPrefixCache(max_bytes=200, block=8)
    cache.insert([1, 2, 3], "short", 10)     # can never match a block
    cache.insert(list(range(10)), "fat", 500)  # exceeds the whole budget
    assert len(cache) == 0 and cache.bytes == 0


def test_lru_eviction_is_oldest_first_and_lookup_refreshes():
    cache = RadixPrefixCache(max_bytes=300, block=4)
    a, b, c, d = ([i, 50 + i, 60 + i, 70 + i, 80 + i, 90 + i]
                  for i in range(4))
    cache.insert(a, "A", 100)
    cache.insert(b, "B", 100)
    cache.insert(c, "C", 100)
    m, key, _ = cache.lookup(a)  # touch A → recency order is now B, C, A
    assert m == 4
    cache.unpin(key)
    cache.insert(d, "D", 100)  # 400 > 300 → evict exactly the oldest: B
    assert cache.evictions == 1 and cache.bytes == 300
    assert cache.lookup(b) == (0, None, None)
    m, key, payload = cache.lookup(a)
    assert (m, payload) == (4, "A")
    cache.unpin(key)


def test_pinned_entries_survive_eviction_until_unpinned():
    cache = RadixPrefixCache(max_bytes=150, block=4)
    a = [1, 2, 3, 4, 5, 6]
    b = [7, 8, 9, 10, 11, 12]
    cache.insert(a, "A", 100)
    m, key, _ = cache.lookup(a)  # pin A (a prefill worker is reading it)
    assert m == 4
    cache.insert(b, "B", 100)  # over budget, but A is pinned → B evicted
    assert cache.lookup(b) == (0, None, None)
    m2, key2, payload = cache.lookup(a)
    assert (m2, payload) == (4, "A")
    cache.unpin(key2)
    cache.unpin(key)
    cache.insert([20, 21, 22, 23, 24, 25], "C", 100)  # now A is fair game
    assert cache.lookup(a) == (0, None, None)
    assert cache.evictions == 2


def test_invalidate_repairs_trie_bottom_up():
    cache = RadixPrefixCache(max_bytes=10_000, block=4)
    pre = [9, 8, 7, 6]
    a, b = pre + [1, 2, 3, 4], pre + [5, 6, 7, 8]
    cache.insert(a, "A", 100)
    cache.insert(b, "B", 100)
    assert cache.invalidate(tuple(a))
    assert not cache.invalidate(tuple(a))  # already gone
    # the shared prefix nodes still index B; A's unique suffix is pruned
    m, key, payload = cache.lookup(pre + [40, 41, 42, 43])
    assert (m, payload) == (4, "B")
    cache.unpin(key)
    m, key, payload = cache.lookup(a)  # only the 4 shared tokens remain
    assert (m, payload) == (4, "B")
    cache.unpin(key)


# -- prefix reuse is token-exact against cold prefill -----------------------


@pytest.mark.parametrize("quantize", [False, True])
def test_prefix_suffix_prefill_bitwise_matches_cold(quantize):
    import jax.numpy as jnp

    eng = build_tiny_engine(slots=2, cache_len=48, quantize=quantize,
                            seed=0)
    donor = [5, 9, 2, 7, 11, 3, 1, 8]
    target = [5, 9, 2, 7, 14, 6]  # shares the first 4 tokens
    entry, nbytes = eng.prefix_entry(eng.prefill_rows(donor, 8))
    assert nbytes > 0
    cold = eng.prefill_rows(target, 8)
    warm = eng.prefill_with_prefix(target, 8, entry, 4)
    assert warm.first_token == cold.first_token
    assert warm.real_len == cold.real_len
    # rows < m depend only on tokens < m under the causal mask, so the
    # donor's rows are not merely close — they are the same bits
    assert jnp.array_equal(warm.payload[0], cold.payload[0])
    assert jnp.array_equal(warm.payload[1], cold.payload[1])
    # and the continuations stay locked token for token
    t_cold = [eng.insert(cold, 0)]
    t_warm = [eng.insert(warm, 1)]
    for _ in range(6):
        out = eng.step([t_cold[-1], t_warm[-1]], [True, True])
        t_cold.append(out[0])
        t_warm.append(out[1])
    assert t_cold == t_warm


def test_prefix_caching_engine_hits_count_and_stay_exact():
    stock = build_tiny_engine(slots=2, cache_len=48, seed=0)
    wrapped = PrefixCachingEngine(
        build_tiny_engine(slots=2, cache_len=48, seed=0),
        cache=RadixPrefixCache(block=4))
    events = []
    wrapped.attach_journal(lambda kind, **d: events.append((kind, d)))
    donor = [5, 9, 2, 7, 11, 3, 1, 8]
    target = [5, 9, 2, 7, 14, 6]
    wrapped.prefill_rows(donor, 8)
    warm = wrapped.prefill_rows(target, 8)
    assert warm.first_token == stock.prefill_rows(target, 8).first_token
    assert (wrapped.hits, wrapped.misses, wrapped.tokens_saved) == (1, 1, 4)
    hit_events = [d for k, d in events
                  if k == JournalEvent.SERVE_PREFIX_HIT]
    assert hit_events and hit_events[0]["saved_tokens"] == 4
    stats = wrapped.stats()
    assert stats["hit_rate"] == 0.5 and stats["entries"] == 2


def test_maybe_wrap_prefix_cache_is_env_gated():
    toy = ToyEngine(slots=1)
    assert maybe_wrap_prefix_cache(toy, enabled=False) is toy
    wrapped = maybe_wrap_prefix_cache(toy, enabled=True)
    assert isinstance(wrapped, PrefixCachingEngine)
    assert wrapped.slots == 1  # passthrough surface


# -- decode_window (the speculative verify leg) -----------------------------


@pytest.mark.parametrize("quantize", [False, True])
def test_decode_window_matches_sequential_steps(quantize):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import decode
    from dlrover_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=32, dim=16, n_layers=2, n_heads=2,
                      n_kv_heads=1, ffn_dim=64, max_seq_len=48,
                      dtype=jnp.float32, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = jnp.asarray([[3, 14, 15, 9, 2, 6]], jnp.int32)
    _, c_win = decode.prefill(params, prompt, cfg, 32, quantize=quantize)
    _, c_seq = decode.prefill(params, prompt, cfg, 32, quantize=quantize)
    toks = [7, 21, 4, 30]
    wl, c_win = decode.decode_window(
        params, jnp.asarray([toks], jnp.int32), c_win, cfg)
    seq_arg = []
    for t in toks:
        lg, c_seq = decode.decode_step(
            params, jnp.asarray([t], jnp.int32), c_seq, cfg)
        seq_arg.append(int(jnp.argmax(lg[0])))
    assert [int(x) for x in jnp.argmax(wl[0], axis=-1)] == seq_arg
    assert int(c_win["pos"]) == int(c_seq["pos"])
    # the window writes the SAME cache rows the sequential steps do, to
    # float tolerance: a (1, K) and a (1, 1) matmul need not round alike
    # (the installed XLA CPU backend does not), and an int8 row may land
    # one quantization step away. Tokens and pos above stay exact.
    for field in ("k", "v") + (("k_scale", "v_scale") if quantize else ()):
        for lw, ls in zip(c_win[field], c_seq[field]):
            np.testing.assert_allclose(
                np.asarray(lw, np.float32), np.asarray(ls, np.float32),
                rtol=1e-5, atol=1 if lw.dtype == jnp.int8 else 1e-6)


# -- speculative decoding: greedy-token-identical to stock decode -----------


def _stock_greedy(spec, prompt, n, quantize=False):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import decode

    out = decode.generate(
        spec._tp, jnp.asarray([list(prompt)], jnp.int32), spec._tc,
        jax.random.PRNGKey(0), n, temperature=0.0,
        quantize_cache=quantize, max_len=len(prompt) + n + spec.k + 1)
    return [int(t) for t in out[0][len(prompt):]]


def test_speculative_matches_stock_greedy():
    spec = build_tiny_spec_pair(seed=0, k=3)
    for prompt in ([4, 9, 1, 16, 3], [1, 2, 3, 4, 5, 6, 7], [30, 2, 17]):
        toks, stats = spec.generate(prompt, 12)
        assert toks == _stock_greedy(spec, prompt, 12)
        assert len(toks) == 12 and stats["rounds"] > 0


def test_speculative_self_draft_accepts_everything():
    spec = build_tiny_spec_pair(seed=0, k=3)
    # drafting WITH the target: every draft is the target's own argmax,
    # so acceptance saturates — and the tokens still match the random
    # drafter's (the draft model affects throughput, never content)
    oracle = SpeculativeDecoder(spec._tp, spec._tc, spec._tp, spec._tc,
                                k=3)
    toks, stats = oracle.generate([4, 9, 1, 16, 3], 12, request_id="r1")
    assert toks == spec.generate([4, 9, 1, 16, 3], 12)[0]
    assert stats["acceptance_rate"] > 0.9
    assert stats["mean_accepted"] > 3.0  # ~k+1 tokens per window step
    assert oracle.sessions["r1"] is stats


def test_speculative_quantized_matches_stock():
    spec = build_tiny_spec_pair(seed=3, k=4, quantize=True)
    prompt = [4, 9, 1, 16, 3]
    toks, _ = spec.generate(prompt, 10)
    assert toks == _stock_greedy(spec, prompt, 10, quantize=True)


# -- int8 batched engine: the quantized cache never changes tokens ----------


@pytest.mark.parametrize("quantize", [False, True])
def test_batched_engine_matches_stock_generate(quantize):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import decode

    eng = build_tiny_engine(slots=3, cache_len=48, quantize=quantize,
                            seed=0)
    prompt = [5, 9, 2, 7, 11, 3]
    toks = [eng.insert(eng.prefill_rows(prompt, 8), 0)]
    for _ in range(9):
        toks.append(eng.step([toks[-1], 0, 0], [True, False, False])[0])
    ref = decode.generate(
        eng.params, jnp.asarray([prompt], jnp.int32), eng.config,
        jax.random.PRNGKey(0), 10, temperature=0.0,
        quantize_cache=quantize, max_len=48)
    assert toks == [int(t) for t in ref[0][len(prompt):]]


# -- open-loop traffic generator --------------------------------------------


def _sched_key(arrivals):
    return [(a.t, tuple(a.prompt), a.max_new_tokens, a.family)
            for a in arrivals]


def test_traffic_schedule_is_deterministic_per_seed():
    def prof(seed):
        return TrafficProfile(rps=40.0, duration_s=2.0, arrival="bursty",
                              diurnal="ramp", seed=seed)

    none = lambda p, m: None  # noqa: E731 — schedule() never submits
    s1 = OpenLoopGenerator(none, prof(11)).schedule()
    s2 = OpenLoopGenerator(none, prof(11)).schedule()
    s3 = OpenLoopGenerator(none, prof(12)).schedule()
    assert s1 and _sched_key(s1) == _sched_key(s2)
    assert _sched_key(s1) != _sched_key(s3)


def test_traffic_prefix_families_share_preambles():
    p = TrafficProfile(rps=60.0, duration_s=2.0, shared_prefix_frac=0.7,
                       seed=11)
    sched = OpenLoopGenerator(lambda *a: None, p).schedule()
    fams = {}
    for a in sched:
        if a.family >= 0:
            fams.setdefault(a.family, []).append(
                tuple(a.prompt[:p.prefix_len]))
    assert fams  # the mixture actually produced family traffic
    for heads in fams.values():
        assert len(set(heads)) == 1  # one fixed preamble per family
    # distinct families carry distinct preambles
    assert len({h[0] for h in fams.values()}) == len(fams)
    # and the length bands are respected
    los = min(lo for _, lo, _ in p.length_mix)
    his = max(hi for _, _, hi in p.length_mix)
    assert all(los <= len(a.prompt) <= his for a in sched)


def test_traffic_burst_and_ramp_shape_the_offered_rate():
    gen = OpenLoopGenerator(lambda *a: None, TrafficProfile(
        rps=30.0, duration_s=4.0, arrival="bursty", burst_factor=4.0,
        diurnal="ramp", seed=0))
    # inside a burst window the envelope towers over the same-phase lull
    assert gen.offered_rps(1.1) > 2.0 * gen.offered_rps(1.6)
    # the ramp makes late lulls hotter than early ones
    assert gen.offered_rps(3.6) > gen.offered_rps(0.6)


def test_percentile_is_nearest_rank():
    assert percentile([], 99) == 0.0
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile([3.0, 1.0, 2.0], 99) == 3.0


# -- chaos: a faulted reuse degrades to cold prefill, never wrong tokens ----


@pytest.mark.chaos
def test_chaos_prefix_reuse_falls_back_to_cold_prefill():
    chaos.configure(f"{SERVE_PREFIX_SITE}:error@nth=1", seed=7)
    events = []
    eng = PrefixCachingEngine(
        ToyEngine(slots=2, vocab=31), cache=RadixPrefixCache(block=4),
        journal_fn=lambda kind, **d: events.append((kind, d)))
    donor = [5, 9, 2, 7, 11, 3, 1, 8]
    target = [5, 9, 2, 7, 14, 6]
    eng.prefill_rows(donor, 8)
    res = eng.prefill_rows(target, 8)  # reuse attempt eats the fault
    assert (eng.hits, eng.dropped) == (0, 1)
    # the answer is the honest cold one, and the request never failed
    ref = ToyEngine(slots=1, vocab=31).prefill_rows(target, 8)
    assert res.first_token == ref.first_token
    dropped = [d for k, d in events
               if k == JournalEvent.SERVE_PREFIX_DROPPED]
    assert dropped and dropped[0]["matched"] == 4
    # the poisoned donor entry is gone; the cold result was re-admitted,
    # so the next family member reuses it (nth=1 is spent)
    eng.prefill_rows(target + [22], 8)
    assert eng.hits == 1


# -- race certification: trie + sessions under churn ------------------------


@pytest.mark.race
def test_prefix_cache_shared_state_race_certified(race_guard):
    """Eviction churn (tiny byte budget) × three shared-prefix traffic
    threads through the batcher's prefill workers × replica-table churn:
    the trie's entry map and the replica table are ``shared``-registered,
    so any unordered access fails the guard."""
    from dlrover_tpu.serving.batcher import ContinuousBatcher
    from dlrover_tpu.serving.registry import ServeReplicaRegistry

    cache = RadixPrefixCache(max_bytes=16 * 40, block=4)
    eng = PrefixCachingEngine(ToyEngine(slots=4, vocab=31), cache=cache)
    batcher = ContinuousBatcher(eng, buckets=(8, 16), prefill_workers=2)
    batcher.start()
    registry = ServeReplicaRegistry()
    stop = threading.Event()
    failures = []

    def churn_registry():
        i = 0
        while not stop.is_set():
            registry.register(i % 3, f"127.0.0.1:{9000 + i % 3}", 2)
            registry.on_node_lost(i % 3)
            i += 1

    def traffic(fam):
        pre = [fam, fam + 1, fam + 2, fam + 3]
        try:
            for i in range(30):
                req = batcher.submit(
                    f"r{fam}-{i}",
                    pre + [(i * 7 + fam) % 31, (i * 5) % 31, i % 31], 2)
                assert req.done.wait(timeout=15.0)
                assert not req.error
        except Exception as e:  # noqa: BLE001 — surface on main thread
            failures.append(e)

    workers = [threading.Thread(target=traffic, args=(f,))
               for f in range(3)]
    reg_thread = threading.Thread(target=churn_registry)
    reg_thread.start()
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=60.0)
    stop.set()
    reg_thread.join(timeout=10.0)
    batcher.stop()
    assert not failures
    assert eng.hits > 0          # family prefixes actually reused
    assert cache.evictions > 0   # the budget actually churned
    assert race_guard.tracked_created > 0
    assert race_guard.races == []


@pytest.mark.race
def test_speculative_sessions_race_certified(race_guard):
    spec = build_tiny_spec_pair(seed=0, k=2, cache_len=48)
    errs = []

    def worker(wid):
        try:
            for i in range(2):
                spec.generate([4 + wid, 9, 1 + i, 16], 6,
                              request_id=f"w{wid}-{i}")
        except Exception as e:  # noqa: BLE001 — surface on main thread
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not errs
    assert len(spec.sessions) == 6
    assert race_guard.tracked_created > 0
    assert race_guard.races == []


# -- the open-loop drill: burst → autoscaler grow, zero loss ----------------


@pytest.mark.serve
def test_traffic_burst_grows_replicas_and_loses_nothing():
    from dlrover_tpu.serving.drill import run_traffic_drill

    result = run_traffic_drill(seed=5)
    assert result["offered"] > 0
    assert result["completed"] == result["offered"]
    assert result["failed"] == 0 and result["lost"] == 0
    assert result["grow_events"] >= 1            # the burst was seen
    assert result["live_replicas_end"] >= 2      # and acted on
    assert result["ttft_p99_s"] > 0.0            # the bench's burst point
    assert result["journal"].get("serve_scale", 0) >= 1
