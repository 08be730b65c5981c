"""Prompt-lookup speculative decoding (models/speculative.py): token-exact
vs plain greedy decode, with fewer device steps when the text repeats."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl.families import FAMILIES
from modelx_tpu.models import llama
from modelx_tpu.models.speculative import (
    SpeculativeDecoder,
    ngram_propose,
    speculative_generate,
)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    def fwd(p, t, kv_cache, cache_offset, mesh=None):
        return llama.forward(p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset)

    return params, cfg, fwd, (lambda b, n: llama.init_kv_cache(cfg, b, n))


class TestNgramPropose:
    def test_proposes_continuation_of_latest_match(self):
        #         0  1  2  3  4  5  6  7
        ids = [5, 6, 7, 8, 9, 5, 6]
        # trailing (5, 6) matched at 0 -> continuation 7, 8, 9
        assert ngram_propose(ids, k=3, max_ngram=2) == [7, 8, 9]
        assert ngram_propose(ids, k=2, max_ngram=2) == [7, 8]

    def test_latest_occurrence_wins(self):
        ids = [1, 2, 3, 1, 2, 4, 1, 2]
        assert ngram_propose(ids, k=1, max_ngram=2) == [4]

    def test_longest_ngram_wins(self):
        ids = [9, 1, 2, 8, 9, 1, 2, 7, 9, 1, 2]
        # 3-gram (9,1,2) matches (latest at 4) -> 7; a 1-gram match would give
        # something else, so the long match must be preferred
        assert ngram_propose(ids, k=1, max_ngram=3) == [7]

    def test_no_match_is_empty(self):
        assert ngram_propose([1, 2, 3, 4], k=4) == []
        assert ngram_propose([], k=4) == []
        assert ngram_propose([1], k=4) == []

    def test_incremental_index_matches_scan(self):
        """The decoder's O(1)-per-token index must answer exactly like the
        one-shot scan, under incremental growth."""
        from modelx_tpu.models.speculative import _NgramIndex

        rng = np.random.RandomState(3)
        seq = rng.randint(0, 5, 40).tolist()  # small alphabet: many repeats
        idx = _NgramIndex(max_ngram=3)
        idx.extend(seq, 0)
        for step in range(30):
            for k in (1, 4):
                assert idx.propose(seq, k) == ngram_propose(seq, k, max_ngram=3), (
                    step, k, seq)
            grown = len(seq)
            seq.extend(rng.randint(0, 5, rng.randint(1, 4)).tolist())
            idx.extend(seq, grown)


class TestExactness:
    def _plain(self, model, prompt, n):
        params, cfg, _fwd, _init = model
        return FAMILIES["llama"].generate(params, jnp.asarray(prompt), cfg, max_new_tokens=n)

    # tier-1 wall: k=4 carries tier-1, the k sweep rides `make slow`
    @pytest.mark.parametrize(
        "k", [pytest.param(1, marks=pytest.mark.slow), 4,
              pytest.param(8, marks=pytest.mark.slow)])
    def test_matches_plain_greedy_on_repetitive_prompt(self, model, k):
        params, _cfg, fwd, init = model
        # a looping prompt: the n-gram lookup should fire constantly
        prompt = np.asarray([[7, 8, 9, 10, 7, 8, 9, 10, 7, 8]], np.int32)
        n = 12
        want = np.asarray(self._plain(model, prompt, n))
        got, stats = speculative_generate(fwd, init, params, prompt, n, k=k)
        np.testing.assert_array_equal(got, want)
        assert stats["device_steps"] >= 1

    def test_matches_plain_greedy_on_arbitrary_prompt(self, model):
        params, _cfg, fwd, init = model
        prompt = np.asarray([[3, 41, 17, 26, 11, 60, 2]], np.int32)
        n = 10
        want = np.asarray(self._plain(model, prompt, n))
        got, stats = speculative_generate(fwd, init, params, prompt, n, k=4)
        np.testing.assert_array_equal(got, want)

    def test_fewer_device_steps_when_model_repeats(self, model):
        """When greedy decode itself settles into a loop (tiny random model
        on a looping prompt usually does), accepted tokens make each device
        step emit >1 token; device_steps must then undercut max_new."""
        params, _cfg, fwd, init = model
        prompt = np.asarray([[5, 6, 5, 6, 5, 6, 5, 6]], np.int32)
        n = 16
        want = np.asarray(self._plain(model, prompt, n))[0, prompt.shape[1]:]
        got, stats = speculative_generate(fwd, init, params, prompt, n, k=8)
        np.testing.assert_array_equal(got[0, prompt.shape[1]:], want)
        # exactness is unconditional; the step win only exists if the
        # model's own continuation is predictable from its past
        uniq = len(set(want.tolist()))
        if uniq <= 3 and stats["accepted"] > 0:
            assert stats["device_steps"] < 1 + n

    def test_budget_respected_exactly(self, model):
        params, _cfg, fwd, init = model
        prompt = np.asarray([[5, 6, 5, 6, 5, 6]], np.int32)
        for n in (1, 2, 5):
            got, stats = speculative_generate(fwd, init, params, prompt, n, k=8)
            assert got.shape == (1, prompt.shape[1] + n)
            # accept-rate honesty: tokens accepted but cut by the budget on
            # the final step must not count
            assert stats["accepted"] <= n

    def test_rejects_multi_row(self, model):
        params, _cfg, fwd, init = model
        with pytest.raises(ValueError):
            speculative_generate(fwd, init, params, np.zeros((2, 4), np.int32), 4)


class TestServeIntegration:
    @pytest.mark.slow  # tier-1 wall: engine-level TestExactness is the tier-1 representative
    def test_server_with_speculation_matches_without(self, model, tmp_path):
        """--speculative-k changes device-step counts, never tokens."""
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer

        params, _cfg, _fwd, _init = model
        d = tmp_path / "m"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"), {k: np.asarray(v) for k, v in params.items()}
        )
        plain = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", name="p")
        spec = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", name="s",
                           speculative_k=6)
        plain.load()
        spec.load()
        prompt = np.asarray([[5, 6, 5, 6, 5, 6]], np.int32)
        a = plain.generate(prompt, max_new_tokens=10)
        b = spec.generate(prompt, max_new_tokens=10)
        np.testing.assert_array_equal(a, b)
        assert spec.stats["spec_device_steps"] >= 1
        # multi-row and sampled requests fall back to the plain paths
        multi = np.asarray([[1, 2], [3, 4]], np.int32)
        np.testing.assert_array_equal(
            plain.generate(multi, max_new_tokens=4),
            spec.generate(multi, max_new_tokens=4),
        )

    def test_stream_chunks_concat_to_generate(self, model):
        """dec.stream's chunks concatenate to exactly dec.generate's output
        (which equals plain greedy); stats accumulate identically."""
        params, _cfg, fwd, init = model
        prompt = [5, 6, 5, 6, 5, 6]
        dec = SpeculativeDecoder(fwd, init, k=4)
        want, want_stats = dec.generate(params, prompt, 10)
        stats = {"device_steps": 0, "proposed": 0, "accepted": 0}
        chunks = list(dec.stream(params, prompt, 10, stats=stats))
        got = [t for c in chunks for t in c[0].tolist()]
        assert got == want
        assert stats == want_stats

    def test_speculative_stream_matches_plain_stream(self, model, tmp_path):
        """HTTP streaming on a --speculative-k server returns the same
        tokens as a plain server's stream (chunk boundaries may differ)."""
        import requests as rq

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
        from modelx_tpu.registry.server import free_port

        params, _cfg, _fwd, _init = model
        d = tmp_path / "m3"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"), {k: np.asarray(v) for k, v in params.items()}
        )
        outs = {}
        for label, k in (("plain", 0), ("spec", 5)):
            server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32",
                                 name=label, speculative_k=k)
            sset = ServerSet({label: server})
            base = f"http://127.0.0.1:{free_port()}"
            httpd = serve(sset, listen=base.rsplit("//", 1)[1])
            try:
                server.load()
                import json as _json

                body = {"tokens": [[5, 6, 5, 6]], "max_new_tokens": 8, "stream": True}
                with rq.post(f"{base}/v1/{label}/generate", json=body, stream=True) as r:
                    assert r.status_code == 200, r.text
                    lines = [_json.loads(ln) for ln in r.iter_lines() if ln]
                assert lines[-1] == {"done": True}
                outs[label] = [t for ln in lines[:-1] for t in ln["tokens"][0]]
                if k:
                    assert server.stats.get("spec_device_steps", 0) >= 1
            finally:
                httpd.shutdown()
        assert outs["spec"] == outs["plain"]

    def test_speculation_not_inert_under_dynamic_batch(self, model, tmp_path):
        """--dynamic-batch routes generates through the batcher; a
        single-row greedy request must still reach the speculative path."""
        import requests as rq

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
        from modelx_tpu.registry.server import free_port

        params, _cfg, _fwd, _init = model
        d = tmp_path / "m2"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"), {k: np.asarray(v) for k, v in params.items()}
        )
        server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", name="s",
                             speculative_k=6)
        sset = ServerSet({"s": server}, dynamic_batch=True)
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            server.load()
            r = rq.post(base + "/v1/generate",
                        json={"tokens": [[5, 6, 5, 6]], "max_new_tokens": 6})
            assert r.status_code == 200, r.text
            assert server.stats.get("spec_device_steps", 0) >= 1
        finally:
            httpd.shutdown()
            for b in sset.batchers.values():
                b.close()


class TestCacheConsistency:
    def test_partial_acceptance_overwrites_rejected_cache(self, model):
        """Drive the decoder for many small steps with k > 1: every rejected
        block position leaves garbage KV that the next step must overwrite
        before the mask exposes it. Exactness over a long horizon is the
        proof."""
        params, cfg, fwd, init = model
        prompt = np.asarray([[1, 2, 3, 1, 2, 3, 9, 1, 2]], np.int32)
        n = 24
        want = np.asarray(
            FAMILIES["llama"].generate(params, jnp.asarray(prompt), cfg, max_new_tokens=n)
        )
        dec = SpeculativeDecoder(fwd, init, k=5, max_ngram=2)
        new, stats = dec.generate(params, prompt[0].tolist(), n)
        np.testing.assert_array_equal(np.asarray(new), want[0, prompt.shape[1]:])


class TestSpeculativeSampling:
    """Modified-rejection acceptance (temperature > 0): the emitted token
    distribution must equal the plain sampler's target distribution
    EXACTLY, no matter what the n-gram draft proposes."""

    def _fixed_forward(self, vocab: int, base_logits):
        """A 'model' whose next-token logits are constant: the target
        distribution is then known in closed form, so empirical output
        frequencies can be chi-square-tested against it."""
        logits = jnp.asarray(base_logits, jnp.float32)

        def fwd(p, t, kv_cache, cache_offset, mesh=None):
            b, s = t.shape
            out = jnp.broadcast_to(logits, (b, s, vocab))
            return out, kv_cache

        return fwd, (lambda b, n: {"pad": jnp.zeros((b, n, 1, 1), jnp.float32)})

    @pytest.mark.slow  # tier-1 wall: ~3000-draw statistical soak
    def test_output_distribution_matches_target(self):
        """~3000 draws of the FIRST post-prefill speculative step (whose
        proposal always fires) vs the closed-form target distribution."""
        vocab = 8
        rng = np.random.RandomState(0)
        base = rng.rand(vocab) * 3
        temp = 0.7
        fwd, init = self._fixed_forward(vocab, base)
        dec = SpeculativeDecoder(fwd, init, k=4, max_ngram=2)
        target = np.asarray(jax.nn.softmax(jnp.asarray(base / temp)))
        # prompt repeats so the trailing 2-gram proposes a continuation:
        # whatever is proposed, acceptance must leave the output ~ target
        prompt = [1, 2, 3, 1, 2]
        counts = np.zeros(vocab)
        n = 3000
        for seed in range(n):
            new, _stats = dec.generate(prompt_ids=prompt, params={},
                                       max_new_tokens=2, temperature=temp,
                                       seed=seed)
            counts[new[1]] += 1  # token 2 = first VERIFY-step token
        freq = counts / n
        # chi-square: sum (O-E)^2/E ~ chi2(v-1); 99.9th pct for df=7 ~ 24.3
        chi2 = float(np.sum((counts - n * target) ** 2 / (n * target)))
        assert chi2 < 24.3, (chi2, freq, target)

    def test_rejection_resample_never_emits_zero_prob_token(self):
        """top-k filtering zeroes most of the vocab; no emitted token may
        fall outside the filtered support (accept OR resample path)."""
        vocab = 16
        base = np.linspace(0, 3, vocab)
        fwd, init = self._fixed_forward(vocab, base)
        dec = SpeculativeDecoder(fwd, init, k=3, max_ngram=2)
        allowed = set(np.argsort(base)[-4:].tolist())  # top_k=4 support
        for seed in range(40):
            new, _ = dec.generate(prompt_ids=[1, 2, 3, 1, 2], params={},
                                  max_new_tokens=6, temperature=1.0,
                                  top_k=4, seed=seed)
            assert set(new) <= allowed, (seed, new)

    def test_top_p_support_respected(self):
        """Nucleus filtering: no emitted token (accept OR resample path)
        may fall outside the top-p nucleus of the known target."""
        vocab = 16
        base = np.linspace(0, 3, vocab)
        fwd, init = self._fixed_forward(vocab, base)
        dec = SpeculativeDecoder(fwd, init, k=3, max_ngram=2)
        # nucleus at p=0.5: smallest prefix of the sorted distribution with
        # cumulative probability >= 0.5 (same rule as ops/sampling.py)
        probs = np.asarray(jax.nn.softmax(jnp.asarray(base)))
        order = np.argsort(-probs)
        cum = np.cumsum(probs[order])
        nucleus = set(order[: int(np.searchsorted(cum, 0.5) + 1)].tolist())
        for seed in range(40):
            new, _ = dec.generate(prompt_ids=[1, 2, 3, 1, 2], params={},
                                  max_new_tokens=6, temperature=1.0,
                                  top_p=0.5, seed=seed)
            assert set(new) <= nucleus, (seed, new, nucleus)

    def test_deterministic_per_seed(self, model):
        params, cfg, fwd, init = model
        dec = SpeculativeDecoder(fwd, init, k=4)
        prompt = [3, 4, 5, 3, 4, 5, 3, 4]
        a, stats_a = dec.generate(params, prompt, 12, temperature=0.9, seed=7)
        b, stats_b = dec.generate(params, prompt, 12, temperature=0.9, seed=7)
        c, _ = dec.generate(params, prompt, 12, temperature=0.9, seed=8)
        assert a == b
        assert len(a) == 12
        assert a != c  # different seed, different stream (overwhelmingly)

    def test_serve_sampled_speculation_routes_and_counts(self, model, tmp_path):
        """--speculative-k now covers sampled single-row requests: the spec
        counters must move for a temperature>0 generate."""
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer

        params, cfg, fwd, init = model
        d = tmp_path / "m"
        d.mkdir()
        st.write_safetensors(str(d / "model.safetensors"),
                             {k: np.asarray(v) for k, v in params.items()})
        srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32",
                          speculative_k=4)
        srv.load()
        out = srv.generate(np.array([[3, 4, 5, 3, 4]], np.int32),
                           max_new_tokens=8, temperature=0.8, seed=5)
        assert out.shape == (1, 13)
        assert srv.stats.get("spec_device_steps", 0) > 0
        # and the stream path: concatenation matches generate for same seed
        pieces = list(srv.generate_stream(
            np.array([[3, 4, 5, 3, 4]], np.int32), max_new_tokens=8,
            temperature=0.8, seed=5))
        got = np.concatenate(pieces, axis=1)
        np.testing.assert_array_equal(got, out[:, 5:])
