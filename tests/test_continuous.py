"""Continuous (in-flight) batching (dl/continuous.py).

The exactness oracle everywhere: a request decoded by the continuous
engine must yield byte-identical tokens to the same request on the plain
paths (ModelServer.generate / ragged decode / ChunkedDecoder stream) —
greedy by argmax determinism, sampled because the per-row (seed, step)
streams are carried per slot."""

import queue
import threading
import time

import numpy as np
import pytest
import requests

import jax
import jax.numpy as jnp

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
from modelx_tpu.registry.server import free_port


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    import dataclasses

    from modelx_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    d = tmp_path_factory.mktemp("continuous")
    st.write_safetensors(
        str(d / "model.safetensors"), {k: np.asarray(v) for k, v in params.items()}
    )
    srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", max_seq_len=96)
    srv.load()
    return srv


# module-scoped: one compiled engine serves every test that doesn't need
# a special configuration — each fresh engine re-jits its whole program
# set, and tier-1 wall time pays for every one of them
@pytest.fixture(scope="module")
def engine(server):
    cb = ContinuousBatcher(server, max_slots=4, chunk_size=4)
    yield cb
    cb.close()


class TestExactness:
    def test_greedy_matches_plain(self, server, engine):
        tokens = np.array([[5, 9, 2, 7, 1]], np.int32)
        expected = server.generate(tokens, max_new_tokens=11)
        got = engine.generate(tokens, max_new_tokens=11)
        np.testing.assert_array_equal(got, expected)

    def test_sampled_matches_ragged(self, server, engine):
        """Same (seed, step) stream as the ragged/stream paths."""
        tokens = np.array([[3, 4, 5]], np.int32)
        expected = server.generate(
            tokens, max_new_tokens=9, temperature=0.8, top_k=12, top_p=0.9, seed=41
        )
        got = engine.generate(
            tokens, max_new_tokens=9, temperature=0.8, top_k=12, top_p=0.9, seed=41
        )
        np.testing.assert_array_equal(got, expected)

    def test_multirow_request(self, server, engine):
        tokens = np.array([[5, 9, 2], [8, 1, 1]], np.int32)
        expected = server.generate(tokens, max_new_tokens=6)
        got = engine.generate(tokens, max_new_tokens=6)
        np.testing.assert_array_equal(got, expected)

    # ~8 s concurrency soak; per-feature exactness (sampled/ragged/stream)
    # stays tier-1
    @pytest.mark.slow
    def test_concurrent_mixed_requests_match_solo(self, server, engine):
        """Requests of different lengths/budgets/sampling, submitted
        concurrently, each match their solo result exactly."""
        import concurrent.futures

        reqs = [
            (np.array([[1, 2, 3]], np.int32), 5, dict()),
            (np.array([[9, 8, 7, 6, 5, 4, 3]], np.int32), 9, dict(temperature=0.7, seed=3)),
            (np.array([[11, 12]], np.int32), 3, dict(temperature=1.1, top_p=0.8, seed=8)),
            (np.array([[30]], np.int32), 1, dict()),
            (np.array([[4, 4, 4, 4]], np.int32), 12, dict(temperature=0.5, top_k=7, seed=5)),
        ]
        expected = [server.generate(t, max_new_tokens=n, **s) for t, n, s in reqs]
        with concurrent.futures.ThreadPoolExecutor(len(reqs)) as pool:
            got = list(pool.map(
                lambda r: engine.generate(r[0], max_new_tokens=r[1], **r[2]), reqs
            ))
        for e, g in zip(expected, got):
            np.testing.assert_array_equal(g, e)

    def test_stream_concatenates_to_generate(self, server, engine):
        tokens = np.array([[2, 4, 6]], np.int32)
        pieces = list(engine.stream(tokens, max_new_tokens=10))
        got = np.concatenate(pieces, axis=1)
        expected = server.generate(tokens, max_new_tokens=10)[:, 3:]
        np.testing.assert_array_equal(got, expected)
        # first piece is the prefill token alone: streaming TTFT is one
        # prefill, not a whole chunk
        assert pieces[0].shape == (1, 1)


class TestScheduling:
    def test_mid_decode_join(self, server, engine):
        """A short request admitted while a long decode runs completes
        WITHOUT waiting for the long decode to finish — the defining
        continuous-batching property."""
        long_tokens = np.array([[7, 7, 7]], np.int32)
        short_tokens = np.array([[9, 1]], np.int32)
        long_done = {}
        short_done = {}

        def long_req():
            long_done["out"] = engine.generate(long_tokens, max_new_tokens=64)
            long_done["t"] = time.monotonic()

        chunks0 = engine.stats["chunks"]  # module-scoped engine: delta
        t_long = threading.Thread(target=long_req)
        t_long.start()
        # wait until the long decode is genuinely mid-flight
        deadline = time.monotonic() + 10
        while engine.stats["chunks"] - chunks0 < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert engine.stats["chunks"] - chunks0 >= 2, "long decode never started"

        short = engine.generate(short_tokens, max_new_tokens=4)
        short_done["t"] = time.monotonic()
        t_long.join()
        assert short_done["t"] < long_done["t"], (
            "short request waited for the long decode to finish"
        )
        np.testing.assert_array_equal(
            short, server.generate(short_tokens, max_new_tokens=4)
        )
        np.testing.assert_array_equal(
            long_done["out"], server.generate(long_tokens, max_new_tokens=64)
        )

    def test_more_requests_than_slots(self, server):
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        try:
            import concurrent.futures

            reqs = [np.array([[i + 1, i + 2]], np.int32) for i in range(5)]
            expected = [server.generate(t, max_new_tokens=5) for t in reqs]
            with concurrent.futures.ThreadPoolExecutor(5) as pool:
                got = list(pool.map(lambda t: cb.generate(t, max_new_tokens=5), reqs))
            for e, g in zip(expected, got):
                np.testing.assert_array_equal(g, e)
        finally:
            cb.close()

    def test_budget_exceeding_max_len_rejected(self, server, engine):
        with pytest.raises(ValueError, match="max_len"):
            engine.generate(np.array([[1, 2]], np.int32), max_new_tokens=1000)

    def test_close_fails_waiters(self, server):
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        out = cb.submit_row([1, 2, 3], 500 // 8, {})
        cb.close()
        # drain: either tokens then an error/DONE — must not hang
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                item = out.get(timeout=5)
            except queue.Empty:
                pytest.fail("waiter hung after close")
            if isinstance(item, BaseException) or item is not None and not isinstance(item, np.ndarray):
                break

    def test_submit_after_close_raises(self, server):
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        cb.close()
        with pytest.raises(RuntimeError, match="closed"):
            cb.submit_row([1], 4, {})

    def test_fifo_admission_under_slot_contention(self, server):
        """Requests that find no free slot wait in ARRIVAL order — the old
        requeue-at-the-back would admit the LATER arrival first each time
        the queue was contended."""
        cb = ContinuousBatcher(server, max_slots=1, chunk_size=4)
        try:
            # record the engine's ADMISSION order (single-threaded in the
            # loop, so race-free — completion timestamps measured by
            # competing drain threads are not: with async token readback
            # back-to-back finishes land ~0.1 ms apart)
            admitted: list = []
            orig_admit = cb._admit_all
            cb._admit_all = lambda preps: (
                admitted.extend(p["ticket"] for p in preps),
                orig_admit(preps),
            )[1]
            a = cb.submit([7, 7, 7], 48, {})
            first = a.out.get(timeout=30)  # A holds the only slot
            assert isinstance(first, np.ndarray)
            b = cb.submit([1, 2], 4, {})
            time.sleep(0.05)  # order the queue arrivals deterministically
            c = cb.submit([3, 4], 4, {})

            def drain(t):
                while True:
                    item = t.out.get(timeout=60)
                    if not isinstance(item, np.ndarray):
                        return

            tb = threading.Thread(target=drain, args=(b,))
            tc = threading.Thread(target=drain, args=(c,))
            tb.start()
            tc.start()
            drain(a)
            tb.join(60)
            tc.join(60)
            assert admitted.index(b) < admitted.index(c), (
                "later arrival was admitted before an earlier one"
            )
        finally:
            cb.close()

    def test_stream_close_cancels_row_and_frees_slot(self, server):
        """Closing a stream generator mid-flight (client disconnect) cancels
        the row: the slot frees at a chunk boundary instead of decoding the
        full budget into a queue nobody drains."""
        cb = ContinuousBatcher(server, max_slots=1, chunk_size=4)
        try:
            gen = cb.stream(np.array([[5, 6]], np.int32), max_new_tokens=60)
            next(gen)  # admitted and decoding
            gen.close()  # GeneratorExit -> ticket.cancel()
            deadline = time.monotonic() + 20
            while cb._rows and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not cb._rows, "cancelled row still holds its slot"
            # the freed slot serves the next request promptly and exactly
            t = np.array([[9, 1]], np.int32)
            np.testing.assert_array_equal(
                cb.generate(t, max_new_tokens=4),
                server.generate(t, max_new_tokens=4),
            )
        finally:
            cb.close()


class TestServingIntegration:
    @pytest.fixture()
    def sset(self, server):
        s = ServerSet({"m": server}, continuous_batch=True, max_slots=4,
                      stream_chunk_size=4)
        yield s
        for cb in s.cbatchers.values():
            cb.close()

    def test_http_generate_and_stream_route_through_engine(self, sset, server):
        port = free_port()
        httpd = serve(sset, listen=f"127.0.0.1:{port}")
        base = f"http://127.0.0.1:{port}"
        try:
            tokens = [[1, 2, 3]]
            r = requests.post(base + "/v1/generate",
                              json={"tokens": tokens, "max_new_tokens": 6})
            assert r.status_code == 200, r.text
            expected = server.generate(np.asarray(tokens, np.int32), max_new_tokens=6)
            np.testing.assert_array_equal(np.asarray(r.json()["tokens"]), expected)

            r = requests.post(
                base + "/v1/generate",
                json={"tokens": tokens, "max_new_tokens": 6, "stream": True},
                stream=True,
            )
            assert r.status_code == 200
            got = []
            for line in r.iter_lines():
                obj = __import__("json").loads(line)
                if obj.get("done"):
                    break
                got.extend(obj["tokens"][0])
            assert got == expected[0, 3:].tolist()
            cb = sset.cbatchers["m"]
            assert cb.stats["admitted"] >= 2  # both requests rode the engine
        finally:
            httpd.shutdown()

    def test_metrics_exposes_continuous_stats(self, sset, server):
        port = free_port()
        httpd = serve(sset, listen=f"127.0.0.1:{port}")
        base = f"http://127.0.0.1:{port}"
        try:
            requests.post(base + "/v1/generate",
                          json={"tokens": [[1, 2]], "max_new_tokens": 2})
            m = requests.get(base + "/metrics").json()
            cont = m["m"]["continuous"]
            assert cont["admitted"] >= 1
            # the operator/bench surface: engine counters + live gauges
            # ride the endpoint (no internals poking needed)
            for key in ("chunks", "active_peak", "prefill_pieces",
                        "stall_ms_max", "active", "filling", "waiting",
                        # pipelined-dispatch gauges (ISSUE 7) ride the same
                        # snapshot: always-present instantaneous values plus
                        # the dispatch counters
                        "dispatch_depth", "tokens_in_flight",
                        "sync_lag_chunks", "dispatches",
                        "host_syncs_per_boundary"):
                assert key in cont, key
        finally:
            httpd.shutdown()

    def test_serverset_wires_prefill_knobs_to_engine(self, server):
        s = ServerSet({"m": server}, continuous_batch=True, max_slots=2,
                      stream_chunk_size=4, prefill_chunk=16, prefill_budget=32,
                      dispatch_depth=3)
        try:
            cb = s.continuous_for(server)
            # wiring only — chunked-decode exactness is covered by
            # TestChunkedPrefill (skipping generate skips a full re-jit)
            assert cb.prefill_chunk == 16
            assert cb.prefill_budget == 32
            assert cb.stats["prefill_chunk"] == 16
            assert cb.dispatch_depth == 3
        finally:
            for cb in s.cbatchers.values():
                cb.close()


class TestLoneShortRequests:
    def test_lone_budget_one_request_completes(self, server):
        """Regression (caught live): a lone 1-token request admits, frees
        its slot immediately, and the loop must still deliver its async
        first token instead of blocking for the next request."""
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        try:
            import concurrent.futures

            tokens = np.array([[7, 8, 9]], np.int32)
            with concurrent.futures.ThreadPoolExecutor(1) as pool:
                fut = pool.submit(cb.generate, tokens, 1)
                out = fut.result(timeout=60)  # hang = the bug
            np.testing.assert_array_equal(
                out, server.generate(tokens, max_new_tokens=1))
            # and again: the engine must be idle-but-healthy afterwards
            out2 = cb.generate(tokens, max_new_tokens=1)
            np.testing.assert_array_equal(out, out2)
        finally:
            cb.close()

    def test_lone_stop_on_first_token_completes(self, server):
        """Same shape with stop_token_ids hitting the prefill token."""
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        try:
            import concurrent.futures

            tokens = np.array([[7, 8, 9]], np.int32)
            first = int(server.generate(tokens, max_new_tokens=1)[0, -1])
            with concurrent.futures.ThreadPoolExecutor(1) as pool:
                fut = pool.submit(
                    lambda: cb.generate(tokens, 8, stop_token_ids=[first]))
                out = fut.result(timeout=60)
            assert out.tolist() == [[7, 8, 9, first]]
        finally:
            cb.close()


class TestContinuousPrefixCache:
    """The engine's admission path uses the PrefixKVCache: a prompt
    extending a stored prefix prefills only its suffix, byte-identically."""

    @pytest.fixture()
    def cached_engine(self, server):
        from modelx_tpu.models.decode import PrefixKVCache

        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4,
                               prefix_cache=PrefixKVCache(4))
        yield cb
        cb.close()

    def test_second_turn_matches_plain(self, server, cached_engine):
        cb = cached_engine
        turn1 = np.array([[3, 4, 5, 6, 7]], np.int32)
        out1 = cb.generate(turn1, max_new_tokens=6)
        np.testing.assert_array_equal(out1, server.generate(turn1, max_new_tokens=6))
        turn2 = np.concatenate([out1, np.array([[9, 9]], np.int32)], axis=1)
        out2 = cb.generate(turn2, max_new_tokens=6)
        np.testing.assert_array_equal(out2, server.generate(turn2, max_new_tokens=6))
        assert cb.prefix_cache.hits == 1
        # sampled turn too (same (seed, step) streams from the suffix admit)
        out3 = cb.generate(turn2, max_new_tokens=5, temperature=0.8, seed=13)
        np.testing.assert_array_equal(
            out3, server.generate(turn2, max_new_tokens=5, temperature=0.8, seed=13))

    def test_entries_stay_prompt_bucketed(self, server, cached_engine):
        """Stored entries must be trimmed to the PROMPT's 16-bucket on both
        the miss and hit admission paths — per-turn bucket growth would
        bloat HBM and eventually evict conversations from the fast path."""
        import jax as _jax

        cb = cached_engine
        t1 = np.array([[3, 4, 5, 6, 7]], np.int32)  # 5 -> bucket 16
        out1 = cb.generate(t1, max_new_tokens=6)
        t2 = np.concatenate([out1, np.array([[9]], np.int32)], axis=1)  # 12 -> 16
        cb.generate(t2, max_new_tokens=6)  # hit path stores too
        with cb.prefix_cache._lock:
            lens = {
                len(k): int(_jax.tree_util.tree_leaves(v)[0].shape[1])
                for k, v in cb.prefix_cache._od.items()
            }
        from modelx_tpu.models.decode import pad_seq_len

        assert lens == {n: pad_seq_len(n) for n in lens}

    # tier-1 wall (ISSUE 16): second_turn_matches_plain keeps the prefix-cache oracle tier-1
    @pytest.mark.slow
    def test_oversize_prefix_falls_back_to_full_prefill(self, server):
        """A stored bucket + suffix bucket that exceeds max_len must
        full-prefill (correctness over reuse) and count as a MISS."""
        from modelx_tpu.models.decode import PrefixKVCache

        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4, max_len=56,
                               prefix_cache=PrefixKVCache(4))
        try:
            t1 = np.array([[(i % 60) + 1 for i in range(17)]], np.int32)
            cb.generate(t1, max_new_tokens=4)  # stores a 32-bucket prefix
            # 17 new tokens: suffix bucket 32; 32 + 32 = 64 > 56 -> unusable
            t2 = np.concatenate(
                [t1, np.array([[(i % 60) + 1 for i in range(17)]], np.int32)], axis=1)
            out2 = cb.generate(t2, max_new_tokens=4)
            np.testing.assert_array_equal(
                out2, server.generate(t2, max_new_tokens=4))
            assert cb.prefix_cache.hits == 0
            assert cb.prefix_cache.misses == 2
        finally:
            cb.close()


class TestBatchedAdmission:
    """A burst of same-bucket arrivals admits as ONE compiled program
    (k dispatch round-trips -> 1) — token-exactly."""

    # ~7 s; mixed-bucket/pow2/multirow admission tests stay tier-1
    @pytest.mark.slow
    def test_burst_groups_and_matches(self, server):
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4)
        try:
            import concurrent.futures

            # same 16-bucket, mixed sampling: one grouped admit program
            reqs = [
                (np.array([[1, 2, 3]], np.int32), 6, dict()),
                (np.array([[9, 8, 7, 6]], np.int32), 6, dict(temperature=0.7, seed=3)),
                (np.array([[11, 12]], np.int32), 5, dict(temperature=1.1, top_p=0.8, seed=8)),
                (np.array([[4, 4, 4, 4, 4]], np.int32), 4, dict(top_k=9, temperature=0.4, seed=2)),
            ]
            expected = [server.generate(t, max_new_tokens=n, **s) for t, n, s in reqs]
            barrier = threading.Barrier(len(reqs))

            def go(r):
                barrier.wait()
                return cb.generate(r[0], max_new_tokens=r[1], **r[2])

            with concurrent.futures.ThreadPoolExecutor(len(reqs)) as pool:
                got = list(pool.map(go, reqs))
            for e, g in zip(expected, got):
                np.testing.assert_array_equal(g, e)
            assert cb.stats.get("admit_batches", 0) >= 1, (
                "simultaneous same-bucket burst never shared an admit program"
            )
        finally:
            cb.close()

    def test_multirow_generate_batches_admissions(self, server):
        """generate()'s B rows arrive together -> grouped admission, and the
        per-row seed streams still match the ragged path."""
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4)
        try:
            tokens = np.array([[5, 9, 2], [8, 1, 1], [3, 3, 3]], np.int32)
            expected = server.generate(tokens, max_new_tokens=7,
                                       temperature=0.9, seed=17)
            got = cb.generate(tokens, max_new_tokens=7, temperature=0.9, seed=17)
            np.testing.assert_array_equal(got, expected)
            assert cb.stats.get("admit_batches", 0) >= 1
        finally:
            cb.close()

    # tier-1 wall (ISSUE 16): mixed_buckets + multirow keep batched admission tier-1
    @pytest.mark.slow
    def test_small_burst_pads_to_pow2_not_max_slots(self, server):
        """A 2-row burst on a max_slots=8 engine must prefill a [2, Sb]
        block, not [8, Sb] — the batched-admit program pads to the next
        power of two of the burst size (up to max_slots/2 x wasted prefill
        FLOPs otherwise), and the tokens stay exact."""
        cb = ContinuousBatcher(server, max_slots=8, chunk_size=4)
        try:
            admit_rows = []
            orig = cb._admit_many_prog

            def spy(params, prompts, *args):
                admit_rows.append(int(prompts.shape[0]))
                return orig(params, prompts, *args)

            cb._admit_many_prog = spy
            tokens = np.array([[5, 9, 2], [8, 1, 1]], np.int32)
            expected = server.generate(tokens, max_new_tokens=6)
            got = cb.generate(tokens, max_new_tokens=6)
            np.testing.assert_array_equal(got, expected)
            assert admit_rows == [2], admit_rows
            assert cb.stats.get("admit_pad_rows", 0) == 0
            # a 3-row burst rounds up to 4 (one pad row), never to 8
            tokens3 = np.array([[5, 9, 2], [8, 1, 1], [3, 3, 3]], np.int32)
            expected3 = server.generate(tokens3, max_new_tokens=5)
            got3 = cb.generate(tokens3, max_new_tokens=5)
            np.testing.assert_array_equal(got3, expected3)
            assert admit_rows == [2, 4], admit_rows
            assert cb.stats.get("admit_pad_rows", 0) == 1
        finally:
            cb.close()

    def test_mixed_buckets_split_groups(self, server):
        """Arrivals in different prompt buckets can't share a program but
        must still all admit correctly at one boundary."""
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4)
        try:
            import concurrent.futures

            reqs = [
                (np.array([[1, 2]], np.int32), 4, dict()),                      # 16-bucket
                (np.array([[i % 50 + 1 for i in range(20)]], np.int32), 4, dict()),  # 32-bucket
                (np.array([[7, 7, 7]], np.int32), 4, dict()),                   # 16-bucket
            ]
            expected = [server.generate(t, max_new_tokens=n, **s) for t, n, s in reqs]
            barrier = threading.Barrier(len(reqs))

            def go(r):
                barrier.wait()
                return cb.generate(r[0], max_new_tokens=r[1], **r[2])

            with concurrent.futures.ThreadPoolExecutor(len(reqs)) as pool:
                got = list(pool.map(go, reqs))
            for e, g in zip(expected, got):
                np.testing.assert_array_equal(g, e)
        finally:
            cb.close()

    def test_prefix_cache_keeps_single_admissions(self, server):
        """With a prefix cache the engine admits one-by-one (the batched
        program has no per-row scratch-KV return) — and stays exact."""
        from modelx_tpu.models.decode import PrefixKVCache

        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4,
                               prefix_cache=PrefixKVCache(4))
        try:
            tokens = np.array([[5, 9, 2], [8, 1, 1]], np.int32)
            expected = server.generate(tokens, max_new_tokens=5)
            got = cb.generate(tokens, max_new_tokens=5)
            np.testing.assert_array_equal(got, expected)
            assert cb.stats.get("admit_batches", 0) == 0
        finally:
            cb.close()


class TestBurstWindow:
    def test_zero_window_disables_the_idle_sleep(self, server):
        """burst_window_ms=0 must serve correctly with no gather pause."""
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4,
                               burst_window_ms=0.0)
        try:
            t = np.array([[5, 9, 2]], np.int32)
            np.testing.assert_array_equal(
                cb.generate(t, max_new_tokens=6),
                server.generate(t, max_new_tokens=6))
        finally:
            cb.close()

    def test_single_slot_engine_skips_the_window(self, server):
        """max_slots=1 can never co-admit a burst; the window must not add
        latency there (and the engine still serves exactly)."""
        cb = ContinuousBatcher(server, max_slots=1, chunk_size=4,
                               burst_window_ms=50.0)
        try:
            t = np.array([[7, 8]], np.int32)
            import time as _t

            t0 = _t.monotonic()
            out = cb.generate(t, max_new_tokens=1)
            # warm call includes compile; the SECOND call shows the per-
            # request cost — with the 50 ms window wrongly applied, three
            # sequential requests would pay >= 150 ms of pure sleep
            for _ in range(3):
                out = cb.generate(t, max_new_tokens=1)
            np.testing.assert_array_equal(
                out, server.generate(t, max_new_tokens=1))
        finally:
            cb.close()


class TestPipelineDepth:
    """Deeper chunk pipelining (dispatch-ahead) must not change tokens —
    plans are value-independent, so depth only moves sync points."""

    @pytest.mark.parametrize("depth", [1, 3])
    # ~11 s over both depths; default-depth exactness runs everywhere else
    @pytest.mark.slow
    def test_depth_variants_match_plain(self, server, depth):
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4,
                               pipeline_depth=depth)
        try:
            tokens = np.array([[5, 9, 2, 7, 1]], np.int32)
            expected = server.generate(tokens, max_new_tokens=13)
            np.testing.assert_array_equal(
                cb.generate(tokens, max_new_tokens=13), expected)
            # concurrent mixed load at this depth too
            import concurrent.futures

            reqs = [
                (np.array([[1, 2, 3]], np.int32), 9, dict()),
                (np.array([[9, 8, 7]], np.int32), 5, dict(temperature=0.7, seed=3)),
                (np.array([[30]], np.int32), 1, dict()),
            ]
            exp = [server.generate(t, max_new_tokens=n, **s) for t, n, s in reqs]
            with concurrent.futures.ThreadPoolExecutor(len(reqs)) as pool:
                got = list(pool.map(
                    lambda r: cb.generate(r[0], max_new_tokens=r[1], **r[2]), reqs))
            for e, g in zip(exp, got):
                np.testing.assert_array_equal(g, e)
        finally:
            cb.close()

    def test_deep_pipeline_stop_tokens_still_cut(self, server):
        """Stop hits lag by up to depth chunks of wasted compute but the
        DELIVERED stream must still cut at the first stop."""
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4,
                               pipeline_depth=3)
        try:
            tokens = np.array([[7, 8, 9]], np.int32)
            plain = server.generate(tokens, max_new_tokens=24)
            gen = plain[0, 3:].tolist()
            stop = gen[5]  # a token ~5 steps in
            got = cb.generate(tokens, max_new_tokens=24, stop_token_ids=[stop])
            # inclusive cut: the delivered row ENDS at the stop token — a
            # full-budget row (stop ignored) must fail here, not pass on a
            # matching greedy prefix
            want = tokens[0].tolist() + gen[: gen.index(stop) + 1]
            assert got[0].tolist() == want
        finally:
            cb.close()


class TestChunkedPrefill:
    """Chunked prefill (prefill_chunk > 0): long prompts land piece by
    piece between decode chunks. The oracle is unchanged — byte-identical
    tokens to the plain paths (ragged decode via server.generate) — plus
    the scheduling property the feature exists for: decode boundaries
    keep firing while a prompt fills."""

    # class-scoped: one compiled engine serves every exactness test here
    # (tier-1 wall time — a per-test engine re-jits the whole program set)
    @pytest.fixture(scope="class")
    def engine(self, server):
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4,
                               prefill_chunk=16)
        yield cb
        cb.close()

    def test_long_greedy_matches_plain(self, server, engine):
        before = engine.stats["prefill_pieces"]
        rng = np.random.RandomState(5)
        tokens = rng.randint(1, 64, (1, 40)).astype(np.int32)  # 3 pieces
        expected = server.generate(tokens, max_new_tokens=11)
        got = engine.generate(tokens, max_new_tokens=11)
        np.testing.assert_array_equal(got, expected)
        assert engine.stats["prefill_pieces"] - before == 3

    # tier-1 wall (ISSUE 16): long_greedy keeps chunked prefill tier-1
    @pytest.mark.slow
    def test_long_sampled_matches_ragged(self, server, engine):
        """Same (seed, step) streams: the flip piece's first token is
        step 0 of the row's stream, like single-program admission."""
        rng = np.random.RandomState(6)
        tokens = rng.randint(1, 64, (1, 37)).astype(np.int32)
        expected = server.generate(
            tokens, max_new_tokens=9, temperature=0.8, top_k=12, top_p=0.9,
            seed=41,
        )
        got = engine.generate(
            tokens, max_new_tokens=9, temperature=0.8, top_k=12, top_p=0.9,
            seed=41,
        )
        np.testing.assert_array_equal(got, expected)

    def test_short_prompt_keeps_single_program_fast_path(self, server, engine):
        before = engine.stats["prefill_pieces"]
        tokens = np.array([[5, 9, 2]], np.int32)  # <= one piece
        np.testing.assert_array_equal(
            engine.generate(tokens, max_new_tokens=5),
            server.generate(tokens, max_new_tokens=5),
        )
        assert engine.stats["prefill_pieces"] == before

    def test_stream_through_chunked_admission(self, server, engine):
        rng = np.random.RandomState(7)
        tokens = rng.randint(1, 64, (1, 33)).astype(np.int32)
        pieces = list(engine.stream(tokens, max_new_tokens=10))
        got = np.concatenate(pieces, axis=1)
        expected = server.generate(tokens, max_new_tokens=10)[:, 33:]
        np.testing.assert_array_equal(got, expected)
        assert pieces[0].shape == (1, 1)  # TTFT is still one token

    def test_multirow_long_prompts_match(self, server, engine):
        rng = np.random.RandomState(8)
        tokens = rng.randint(1, 64, (2, 35)).astype(np.int32)
        expected = server.generate(tokens, max_new_tokens=6)
        got = engine.generate(tokens, max_new_tokens=6)
        np.testing.assert_array_equal(got, expected)

    def test_no_decode_boundary_skipped_while_filling(self, server, engine):
        """THE jitter regression: while a long prompt fills into a batch
        with active decode rows, every prefill piece rides a boundary
        that also dispatched a decode chunk — a monolithic admission (or
        back-to-back pieces) would stall the decoding client for the
        whole prompt. Spies ride the SHARED engine and are restored."""
        cb = engine
        order: list[str] = []
        orig_chunk, orig_piece, orig_flip = (
            cb._chunk, cb._piece_prog, cb._piece_flip_prog
        )
        chunks0 = cb.stats["chunks"]
        try:
            cb._chunk = (
                lambda *a, **kw: (order.append("C"), orig_chunk(*a, **kw))[1]
            )
            cb._piece_prog = lambda *a: (order.append("P"), orig_piece(*a))[1]
            cb._piece_flip_prog = (
                lambda *a: (order.append("P"), orig_flip(*a))[1]
            )
            rng = np.random.RandomState(9)
            dec_tokens = rng.randint(1, 64, (1, 5)).astype(np.int32)
            long_tokens = rng.randint(1, 64, (1, 48)).astype(np.int32)
            res = {}
            t = threading.Thread(
                target=lambda: res.update(
                    dec=cb.generate(dec_tokens, max_new_tokens=40))
            )
            t.start()
            deadline = time.monotonic() + 30
            while cb.stats["chunks"] - chunks0 < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert cb.stats["chunks"] - chunks0 >= 2, "decode row never started"
            res["long"] = cb.generate(long_tokens, max_new_tokens=4)
            t.join()
        finally:
            cb._chunk, cb._piece_prog, cb._piece_flip_prog = (
                orig_chunk, orig_piece, orig_flip
            )
        np.testing.assert_array_equal(
            res["dec"], server.generate(dec_tokens, max_new_tokens=40))
        np.testing.assert_array_equal(
            res["long"], server.generate(long_tokens, max_new_tokens=4))
        seq = "".join(order)
        assert seq.count("P") == 3, seq  # 48 tokens -> 3 pieces
        assert "PP" not in seq, (
            f"decode boundary skipped while filling: {seq}"
        )

    @pytest.mark.slow
    def test_budget_caps_extra_pieces_per_boundary(self, server):
        """Two concurrent long fills under a tight budget: only the head
        piece may land per boundary (the budget exempts it so fills can't
        starve), and both streams stay exact."""
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4,
                               prefill_chunk=16, prefill_budget=16)
        try:
            rng = np.random.RandomState(10)
            a = rng.randint(1, 64, (1, 40)).astype(np.int32)
            b = rng.randint(1, 64, (1, 40)).astype(np.int32)
            tickets = cb.submit_many([
                (a[0].tolist(), 5, {}), (b[0].tolist(), 5, {}),
            ])
            rows = []
            for tk in tickets:
                parts = []
                while True:
                    item = tk.out.get(timeout=60)
                    if not isinstance(item, np.ndarray):
                        assert item is None or not isinstance(item, BaseException)
                        break
                    parts.append(item)
                rows.append(np.concatenate(parts, axis=1))
            np.testing.assert_array_equal(
                np.concatenate([a, rows[0]], axis=1),
                server.generate(a, max_new_tokens=5))
            np.testing.assert_array_equal(
                np.concatenate([b, rows[1]], axis=1),
                server.generate(b, max_new_tokens=5))
            # both prompts chunked (3 pieces each); the 16-token budget
            # admits only the (exempt) head piece per boundary, so the
            # fills complete sequentially — and still exactly
            assert cb.stats["prefill_pieces"] == 6
        finally:
            cb.close()

    @pytest.mark.slow
    def test_cancel_mid_fill_frees_slot(self, server):
        """A consumer that disappears while its prompt is still filling:
        the fill retires at the next boundary (nothing was emitted) and
        the slot serves the next request exactly."""
        cb = ContinuousBatcher(server, max_slots=1, chunk_size=4,
                               prefill_chunk=16)
        try:
            rng = np.random.RandomState(11)
            long_ids = rng.randint(1, 64, 64).astype(np.int32).tolist()
            ticket = cb.submit(long_ids, 16, {})
            deadline = time.monotonic() + 30
            while not cb.stats["prefill_pieces"] and time.monotonic() < deadline:
                time.sleep(0.002)
            ticket.cancel()
            deadline = time.monotonic() + 20
            while (cb._filling or cb._rows) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not cb._filling and not cb._rows
            t = np.array([[9, 1]], np.int32)
            np.testing.assert_array_equal(
                cb.generate(t, max_new_tokens=4),
                server.generate(t, max_new_tokens=4))
        finally:
            cb.close()

    def test_metrics_snapshot_carries_engine_counters(self, server, engine):
        rng = np.random.RandomState(12)
        tokens = rng.randint(1, 64, (1, 40)).astype(np.int32)
        engine.generate(tokens, max_new_tokens=4)
        snap = engine.snapshot()
        for key in ("chunks", "admitted", "active_peak", "prefill_pieces",
                    "stall_ms_max", "active", "filling", "waiting",
                    "pad_fraction"):
            assert key in snap, key
        assert snap["prefill_pieces"] >= 3
        # padded row-chunks / dispatched row-chunks; one live row in a
        # multi-slot engine is mostly padding, and never more than all of it
        assert 0.0 <= snap["pad_fraction"] < 1.0


class TestChunkedPrefillPrefixCache:
    """Prefix-cache hits seed the filling row's offset: only the suffix
    chunk-prefills, and flipped rows store their prompt KV like the
    single-program paths do."""

    @pytest.fixture(scope="class")
    def cached_engine(self, server):
        from modelx_tpu.models.decode import PrefixKVCache

        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4,
                               prefill_chunk=16, prefix_cache=PrefixKVCache(4))
        yield cb
        cb.close()

    # ~8 s; prefix-cache+engine second-turn exactness keeps this covered
    @pytest.mark.slow
    def test_hit_chunk_fills_only_the_suffix(self, server, cached_engine):
        cb = cached_engine
        pieces0, hits0 = cb.stats["prefill_pieces"], cb.prefix_cache.hits
        rng = np.random.RandomState(13)
        turn1 = rng.randint(1, 64, (1, 20)).astype(np.int32)
        out1 = cb.generate(turn1, max_new_tokens=5)
        np.testing.assert_array_equal(
            out1, server.generate(turn1, max_new_tokens=5))
        pieces_turn1 = cb.stats["prefill_pieces"]
        assert pieces_turn1 - pieces0 == 2  # 20 tokens, cold
        turn2 = np.concatenate(
            [out1, rng.randint(1, 64, (1, 20)).astype(np.int32)], axis=1
        )  # 45 tokens, 20 stored -> 25-token suffix = 2 pieces (not 3)
        out2 = cb.generate(turn2, max_new_tokens=5)
        np.testing.assert_array_equal(
            out2, server.generate(turn2, max_new_tokens=5))
        assert cb.prefix_cache.hits - hits0 == 1
        assert cb.stats["prefill_pieces"] - pieces_turn1 == 2
        # sampled third turn over the stored (flip-snapped) prefix
        out3 = cb.generate(turn2, max_new_tokens=5, temperature=0.8, seed=13)
        np.testing.assert_array_equal(
            out3, server.generate(turn2, max_new_tokens=5, temperature=0.8,
                                  seed=13))
        assert cb.prefix_cache.hits - hits0 == 2

    def test_flip_stores_prompt_bucketed_entry(self, server, cached_engine):
        import jax as _jax

        from modelx_tpu.models.decode import pad_seq_len

        cb = cached_engine
        rng = np.random.RandomState(14)
        tokens = rng.randint(1, 64, (1, 40)).astype(np.int32)
        cb.generate(tokens, max_new_tokens=4)
        key = tuple(int(t) for t in tokens[0])
        with cb.prefix_cache._lock:
            entry = cb.prefix_cache._od[key]
            stored_len = int(_jax.tree_util.tree_leaves(entry)[0].shape[1])
        assert stored_len == pad_seq_len(40)


class TestRaggedDecodeAttention:
    """The engine with the ragged decode kernel asked for by name (pallas
    interpret mode here; on one TPU device the same shapes' bigger cousins
    take it unasked): rows read only the KV blocks their context reaches,
    and the tokens are the reference path's."""

    @pytest.fixture(scope="class")
    def ragged(self, server):
        import copy
        import dataclasses

        from modelx_tpu.models import llama

        def decode_fns(cfg, mesh=None):
            def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
                return llama.forward(p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
                                     mesh=mesh, attention_impl="ragged+interpret")
            return fwd, (lambda b, max_len: llama.init_kv_cache(cfg, b, max_len))

        named = copy.copy(server)
        named.family = dataclasses.replace(server.family, decode_fns=decode_fns)
        cb = ContinuousBatcher(named, max_slots=4, chunk_size=4)
        yield cb
        cb.close()

    @pytest.mark.parametrize("prompt,new", [(5, 11), (16, 50), (40, 40), (3, 70)])
    def test_greedy_tokens_are_the_reference_paths(self, server, ragged, prompt, new):
        """Contexts that end in the first 32-position block, cross into the
        second and reach the third of a 96-position cache."""
        tokens = np.random.default_rng(prompt).integers(1, 64, (1, prompt)).astype(np.int32)
        np.testing.assert_array_equal(ragged.generate(tokens, max_new_tokens=new),
                                      server.generate(tokens, max_new_tokens=new))

    def test_sampled_rows_at_different_depths(self, server, ragged):
        prompts = [np.array([[3, 4, 5]], np.int32), np.arange(1, 31, dtype=np.int32)[None]]
        kw = dict(max_new_tokens=24, temperature=0.8, top_k=12, top_p=0.9, seed=41)
        outs: list = [None, None]

        def run(i):
            outs[i] = ragged.generate(prompts[i], **kw)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for tokens, out in zip(prompts, outs):
            np.testing.assert_array_equal(out, server.generate(tokens, **kw))

    def test_the_engine_counts_the_positions_its_blocks_cover(self, server, ragged, engine):
        ragged.generate(np.array([[5, 9, 2, 7, 1]], np.int32), max_new_tokens=40)
        snap = ragged.snapshot()
        steps = snap["chunks"] * ragged.chunk_size
        layers, block = server.cfg.num_layers, 32
        assert snap["attn_kv_positions_cached"] == steps * 4 * 96 * layers
        read = snap["attn_kv_positions_read"]
        # idle slots stay at offset 0 onwards (one block a step and layer);
        # a live row past position 32 reads two
        assert read % block == 0
        assert steps * 4 * block * layers < read < snap["attn_kv_positions_cached"] / 2
        # an engine none of whose layers took the kernel has no such counter
        engine.generate(np.array([[5, 9, 2]], np.int32), max_new_tokens=6)
        assert not any(k.startswith("attn_kv") for k in engine.snapshot())


def record_idle_offsets(cb) -> list:
    """Every later dispatch of ``cb`` appends the offsets it hands the
    device for the slots that hold no row."""
    seen, chunk_args = [], cb._chunk_args

    def recording(filtered):
        seen.append(cb._offsets[cb._free].copy())
        return chunk_args(filtered)

    cb._chunk_args = recording
    return seen


@pytest.mark.parametrize("page_size", [0, 16], ids=["dense", "paged"])
def test_an_idle_slots_offset_is_held_at_zero(server, page_size):
    """A slot without a row decodes garbage from offset 0 in every dispatch:
    its offset does not drift upwards with the live rows' (attention over a
    cache would read ever more for it), before its first row and after its
    last."""
    cb = ContinuousBatcher(server, max_slots=4, chunk_size=4, page_size=page_size)
    try:
        seen = record_idle_offsets(cb)
        tokens = np.array([[5, 9, 2, 7, 1]], np.int32)
        first = cb.generate(tokens, max_new_tokens=30)
        np.testing.assert_array_equal(cb.generate(tokens, max_new_tokens=30), first)
        assert len(seen) >= 4 and all(len(idle) >= 3 for idle in seen)
        assert not np.concatenate(seen).any()
        assert not cb._offsets.any()
    finally:
        cb.close()


class TestOtherFamilies:
    def test_gpt2_engine_clamps_to_n_positions_and_matches(self, tmp_path):
        """ServerSet.continuous_for must cap the engine's max_len at gpt2's
        wpe table (positions past it clamp silently inside jit), and the
        engine's output must still match the plain path."""
        from modelx_tpu.models import gpt2

        cfg = gpt2.GPT2Config.tiny()  # n_positions=64
        params = gpt2.init_params(cfg, jax.random.PRNGKey(2))
        d = tmp_path / "g"
        d.mkdir()
        st.write_safetensors(str(d / "model.safetensors"),
                             {k: np.asarray(v) for k, v in params.items()})
        srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32",
                          max_seq_len=2048, name="g")
        srv.load()
        sset = ServerSet({"g": srv}, continuous_batch=True, max_slots=2,
                         stream_chunk_size=4)
        try:
            cb = sset.continuous_for(srv)
            assert cb.max_len == cfg.n_positions
            tokens = np.array([[5, 6, 7]], np.int32)
            np.testing.assert_array_equal(
                cb.generate(tokens, max_new_tokens=6),
                srv.generate(tokens, max_new_tokens=6))
            # budget past the clamped context is refused, not garbage
            with pytest.raises(ValueError, match="max_len"):
                cb.generate(tokens, max_new_tokens=64)
        finally:
            for c in sset.cbatchers.values():
                c.close()


FIVE = ("tokens", "edge", "filling", "vacant_queued", "vacant_idle")


class TestRowStepLedger:
    """``stats["row_steps"]``: every one of the ``max_slots x n_steps``
    row-steps a chunk program computes stands in exactly one of five
    counters, and ``tokens`` plus the admissions' first tokens is what the
    clients were handed."""

    @pytest.fixture(scope="class")
    def churned(self, server):
        """One run with a case for each counter: a lone row beside vacant
        slots, a request that reaches the queue between the admission sweep
        and the dispatch, a prompt that lands in pieces beside a decoding
        row, more requests than slots, budgets that end mid-program, and a
        stop token that cuts a take at delivery."""
        import concurrent.futures

        cb = ContinuousBatcher(server, max_slots=3, chunk_size=4, prefill_chunk=16)
        seen: list[tuple] = []  # (the ledger, the steps dispatched) after every dispatch
        handed: list[int] = []  # tokens each request's client got
        steps = [0]
        late: list = []
        orig_dispatch, orig_depth = cb._dispatch_chunk, cb._pick_depth

        def dispatch():
            out = orig_dispatch()
            steps[0] += out[2] * cb.chunk_size
            seen.append((dict(cb.stats["row_steps"]), steps[0]))
            return out

        def depth():
            picked = orig_depth()
            if late and len(cb._rows) == 1 and not cb._filling:
                # held in the queue while two slots stand vacant
                late.pop()()
            return picked

        def run(tokens, budget, **kw):
            out = cb.generate(tokens, max_new_tokens=budget, **kw)
            handed.append(out.shape[1] - tokens.shape[1])
            return out

        cb._dispatch_chunk, cb._pick_depth = dispatch, depth
        try:
            rng = np.random.RandomState(3)
            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                lone = rng.randint(1, 64, (1, 5)).astype(np.int32)
                futs = [pool.submit(run, lone, 60)]
                deadline = time.monotonic() + 60
                while cb.stats["chunks"] < 2 and time.monotonic() < deadline:
                    time.sleep(0.005)
                idle_then = cb.stats["row_steps"]["vacant_idle"]
                queued = rng.randint(1, 64, (1, 3)).astype(np.int32)
                late.append(lambda: futs.append(pool.submit(run, queued, 6)))
                while late and time.monotonic() < deadline:
                    time.sleep(0.005)
                # a prompt of three pieces beside the decoding rows
                futs.append(pool.submit(
                    run, rng.randint(1, 64, (1, 40)).astype(np.int32), 7))
                # more requests than slots, budgets that end mid-program
                for budget in (3, 5, 9, 10, 13):
                    futs.append(pool.submit(
                        run, rng.randint(1, 64, (1, 4)).astype(np.int32), budget))
                # a stop token six steps in: the rest of its take gives nothing
                stopped = np.array([[7, 8, 9]], np.int32)
                gen = server.generate(stopped, max_new_tokens=24)[0, 3:].tolist()
                futs.append(pool.submit(run, stopped, 24, stop_token_ids=[gen[5]]))
                for f in list(futs):
                    f.result(timeout=120)
            deadline = time.monotonic() + 30
            while ((cb._rows or cb._tokens_in_flight or cb._inflight_chunks)
                   and time.monotonic() < deadline):
                time.sleep(0.01)  # what was in flight past a stop is delivered too
            yield {"cb": cb, "seen": seen, "handed": handed, "idle_then": idle_then,
                   "snapshot": cb.snapshot()}
        finally:
            cb._dispatch_chunk, cb._pick_depth = orig_dispatch, orig_depth
            cb.close()

    def test_the_five_sum_to_every_row_step_after_every_dispatch(self, churned):
        assert len(churned["seen"]) > 10
        for ledger, steps in churned["seen"]:
            assert sum(ledger[k] for k in FIVE) == ledger["total"] == 3 * steps
            assert min(ledger.values()) >= 0

    def test_tokens_and_first_tokens_are_what_the_clients_were_handed(self, churned):
        snap = churned["snapshot"]
        assert snap["admitted"] == len(churned["handed"]) == 9
        assert snap["row_steps"]["tokens"] + snap["admitted"] == sum(churned["handed"])
        # the stop cut its request short of its budget, so tokens moved to edge
        assert sum(churned["handed"]) < 60 + 6 + 7 + 3 + 5 + 9 + 10 + 13 + 24

    @pytest.mark.parametrize("name", FIVE[1:])
    def test_each_kind_of_step_without_a_token_is_counted_in_its_case(self, churned, name):
        assert churned["snapshot"]["row_steps"][name] > 0
        if name == "vacant_idle":
            # the lone row's first programs: two vacant slots and nothing waiting
            assert churned["idle_then"] >= 2 * 4

    def test_pad_rows_are_counted_as_before_and_leave_the_edge_out(self, churned):
        snap = churned["snapshot"]
        ledger = snap["row_steps"]
        vacant = ledger["vacant_queued"] + ledger["vacant_idle"]
        assert snap["decode_rows"] * 4 == ledger["total"]
        assert snap["decode_pad_rows"] * 4 == vacant  # no filling slot, no edge in it
        assert snap["pad_fraction"] == round(vacant / ledger["total"], 4)

    def test_a_snapshot_is_a_copy_that_does_not_grow_under_its_reader(self, churned):
        cb = churned["cb"]
        snap = cb.snapshot()
        held = dict(snap["row_steps"])
        cb.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=6)
        assert snap["row_steps"] == held
        assert cb.snapshot()["row_steps"]["total"] > held["total"]
        assert set(held) == set(FIVE) | {"total"}


@pytest.mark.parametrize("attr, name", [
    ("_admit_prog", "admit"), ("_admit_cached_prog", "admit_cached"),
    ("_admit_many_prog", "admit_many"), ("_piece_prog", "piece"),
    ("_piece_flip_prog", "piece_flip"), ("_seed_prog", "seed"),
    ("_snap_prog", "snap"), ("_spec_prog", "spec_verify")])
def test_a_programs_xla_module_is_named_for_the_engine_program_it_is(engine, attr, name):
    """A device trace's ``XLA Modules`` line shows ``jit_<function name>``:
    each program the engine jits says there which one it is, as the chunk
    says its depth (``benchmark``'s ``module_share`` tells them by
    ``chunk_impl_``)."""
    prog = getattr(engine, attr)
    assert prog.name == name
    jitted = prog.jit.__name__
    assert jitted.startswith("_" + name) and "lambda" not in jitted
    others = {getattr(engine, a).jit.__name__ for a in (
        "_admit_prog", "_admit_cached_prog", "_admit_many_prog", "_piece_prog",
        "_piece_flip_prog", "_seed_prog", "_snap_prog", "_spec_prog") if a != attr}
    assert jitted not in others and "chunk_impl_" not in jitted
