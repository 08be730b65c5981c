"""Family-aware + multi-tenant serving (dl/serve.py, dl/families.py):
every model family served from its self-describing checkpoint, and N models
behind one HTTP front (BASELINE config #5: concurrent pull+serve)."""

import json

import numpy as np
import pytest
import requests

import jax.numpy as jnp

from modelx_tpu.dl import families as fam
from modelx_tpu.dl.families import FAMILIES
from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
from modelx_tpu.registry.server import free_port


def _write_checkpoint(dirpath, params):
    dirpath.mkdir(parents=True, exist_ok=True)
    st.write_safetensors(
        str(dirpath / "model.safetensors"),
        {k: np.asarray(v) for k, v in params.items()},
    )
    return str(dirpath)


@pytest.fixture(scope="module")
def checkpoints(tmp_path_factory):
    """Tiny fp32 checkpoints, one per family."""
    import jax

    root = tmp_path_factory.mktemp("families")
    out = {}

    from modelx_tpu.models import bert, gemma2, gpt2, llama, mixtral, phi3

    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    import dataclasses

    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    out["llama"] = _write_checkpoint(root / "llama", llama.init_params(cfg, jax.random.PRNGKey(0)))

    g = gpt2.GPT2Config.tiny()
    out["gpt2"] = _write_checkpoint(root / "gpt2", gpt2.init_params(g, jax.random.PRNGKey(1)))

    b = bert.BertConfig.tiny()
    out["bert"] = _write_checkpoint(root / "bert", bert.init_params(b, jax.random.PRNGKey(2)))

    m = dataclasses.replace(mixtral.MixtralConfig.tiny(vocab_size=64), dtype=jnp.float32)
    out["mixtral"] = _write_checkpoint(root / "mixtral", mixtral.init_params(m, jax.random.PRNGKey(3)))

    g2 = dataclasses.replace(gemma2.Gemma2Config.tiny(vocab_size=64), dtype=jnp.float32)
    out["gemma2"] = _write_checkpoint(root / "gemma2", gemma2.init_params(g2, jax.random.PRNGKey(4)))

    p3 = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                             dtype=jnp.float32, tie_embeddings=False)
    out["phi3"] = _write_checkpoint(root / "phi3", phi3.init_params(p3, jax.random.PRNGKey(5)))
    return out


class TestFamilyDetection:
    def test_detect_each_family(self, checkpoints):
        for name, d in checkpoints.items():
            infos, _ = st.read_header_from_file(d + "/model.safetensors")
            assert fam.detect(list(infos)).name == name

    def test_unknown_raises(self):
        with pytest.raises(ValueError, match="family"):
            fam.detect(["mystery.weight"])


class TestFamilyServing:
    @pytest.mark.parametrize("family", ["llama", "gpt2", "mixtral", "bert", "gemma2", "phi3"])
    def test_load_and_forward(self, checkpoints, family):
        server = ModelServer(checkpoints[family], mesh_spec="dp=1", dtype="float32", name=family)
        stats = server.load()
        assert stats["family"] == family
        out = server.forward_argmax(np.array([[1, 2, 3, 4]], np.int32))
        assert out.shape[0] == 1 and out.shape[1] == 4

    def test_generate_causal(self, checkpoints):
        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32")
        server.load()
        out = server.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=2)
        assert out.shape == (1, 5)

    def test_generate_on_bert_rejected(self, checkpoints):
        server = ModelServer(checkpoints["bert"], mesh_spec="dp=1", dtype="float32")
        server.load()
        with pytest.raises(ValueError, match="not generative"):
            server.generate(np.array([[1, 2]], np.int32))


class TestMultiTenant:
    @pytest.fixture(scope="class")
    def front(self, checkpoints):
        servers = {
            "lm": ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32", name="lm"),
            "enc": ModelServer(checkpoints["bert"], mesh_spec="dp=1", dtype="float32", name="enc"),
        }
        sset = ServerSet(servers, default="lm")
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        sset.load_all(concurrent=True)
        yield base
        httpd.shutdown()

    def test_healthz_ready(self, front):
        assert requests.get(front + "/healthz").status_code == 200

    def test_draining_flips_healthz_but_keeps_serving(self, checkpoints):
        """Graceful drain: /healthz goes 503 (LB stops routing) while
        inference routes keep answering in-flight traffic."""
        from modelx_tpu.dl.serve import ModelServer, ServerSet, serve

        server = ModelServer(
            checkpoints["llama"], mesh_spec="dp=1", dtype="float32", name="d"
        )
        sset = ServerSet({"d": server})
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            server.load()
            assert requests.get(base + "/healthz").status_code == 200
            sset.draining = True
            r = requests.get(base + "/healthz")
            assert r.status_code == 503 and r.json()["status"] == "draining"
            r = requests.post(base + "/v1/forward", json={"tokens": [[1, 2]]})
            assert r.status_code == 200  # in-flight traffic still served
        finally:
            httpd.shutdown()

    def test_models_inventory(self, front):
        inv = requests.get(front + "/v1/models").json()
        assert inv["default"] == "lm"
        assert set(inv["models"]) == {"lm", "enc"}
        assert all(m["ready"] for m in inv["models"].values())

    def test_default_model_route(self, front):
        r = requests.post(front + "/v1/forward", json={"tokens": [[1, 2, 3]]})
        assert r.status_code == 200
        assert len(r.json()["logits_argmax"][0]) == 3

    def test_named_model_route(self, front):
        r = requests.post(front + "/v1/enc/forward", json={"tokens": [[1, 2, 3]]})
        assert r.status_code == 200

    def test_unknown_model_404(self, front):
        r = requests.post(front + "/v1/nope/forward", json={"tokens": [[1]]})
        assert r.status_code == 404

    def test_generate_on_encoder_400(self, front):
        r = requests.post(front + "/v1/enc/generate", json={"tokens": [[1]]})
        assert r.status_code == 400

    def test_trace_endpoint(self, front):
        agg = requests.get(front + "/v1/trace").json()
        assert any(p.startswith("serve.load") for p in agg)

    def test_non_dict_body_is_400(self, front):
        """A JSON array/string/number body must be a 400, not a dropped
        connection from an uncaught TypeError."""
        for body in ([1, 2, 3], "tokens", 7):
            r = requests.post(front + "/v1/forward", json=body)
            assert r.status_code == 400, body
            assert "JSON object" in r.json()["error"]

    def test_max_new_tokens_bounded(self, front):
        from modelx_tpu.dl.serve import DEFAULT_MAX_NEW_TOKENS_LIMIT

        for n in (0, -4, DEFAULT_MAX_NEW_TOKENS_LIMIT + 1, 10**9):
            r = requests.post(
                front + "/v1/generate", json={"tokens": [[1, 2]], "max_new_tokens": n}
            )
            assert r.status_code == 400, n
        r = requests.post(
            front + "/v1/generate", json={"tokens": [[1, 2]], "max_new_tokens": "soon"}
        )
        assert r.status_code == 400
        r = requests.post(
            front + "/v1/generate", json={"tokens": [[1, 2]], "max_new_tokens": 2}
        )
        assert r.status_code == 200

    def test_text_plus_tokens_ambiguous_400(self, front):
        """Both text and tokens in one request must 400 — generating from
        the tokens while dropping the text would answer the wrong prompt."""
        r = requests.post(
            front + "/v1/generate",
            json={"text": "hi", "tokens": [[1, 2]], "max_new_tokens": 2},
        )
        assert r.status_code == 400
        assert "either" in r.json()["error"]

    def test_out_of_vocab_token_ids_400(self, front):
        """Ids beyond the embedding table must 400: inside jit the gather
        silently CLAMPS out-of-range ids and returns plausible garbage."""
        for bad in ([[0, 10**6]], [[-1, 2]]):
            r = requests.post(front + "/v1/forward", json={"tokens": bad})
            assert r.status_code == 400, bad
            assert "token ids" in r.json()["error"]
        # beyond int32: numpy raises OverflowError before the vocab check —
        # still a 400 JSON response, never a dropped connection
        for bad in ([[2**31]], [[None, 2]], None):
            r = requests.post(front + "/v1/forward", json={"tokens": bad})
            assert r.status_code == 400, bad
        r = requests.post(
            front + "/v1/generate", json={"tokens": [[0, 10**6]], "max_new_tokens": 2}
        )
        assert r.status_code == 400

    def test_profile_seconds_validated_consistently(self, front):
        from modelx_tpu.dl.serve import MAX_PROFILE_SECONDS

        # above the cap is rejected, not silently truncated to a shorter sleep
        r = requests.post(
            front + "/v1/profile", json={"seconds": MAX_PROFILE_SECONDS + 1}
        )
        assert r.status_code == 400
        r = requests.post(front + "/v1/profile", json={"seconds": "a while"})
        assert r.status_code == 400


class TestDynamicBatching:
    def test_concurrent_requests_coalesce_and_match(self, checkpoints):
        """N concurrent forwards through the batcher return exactly the
        per-request results while issuing fewer device calls."""
        import concurrent.futures

        from modelx_tpu.dl.serve import Batcher

        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32")
        server.load()
        batcher = Batcher(server, window_ms=50)
        try:
            prompts = [
                np.array([[i + 1, i + 2, i + 3, i + 4]], np.int32) for i in range(8)
            ] + [np.array([[7, 8]], np.int32)]  # a shorter one pads
            expected = [server.forward_argmax(p) for p in prompts]
            with concurrent.futures.ThreadPoolExecutor(9) as pool:
                got = list(pool.map(batcher.forward_argmax, prompts))
            for e, g in zip(expected, got):
                np.testing.assert_array_equal(e, g)
            assert batcher.batches < len(prompts)  # actually coalesced
        finally:
            batcher.close()

    def test_error_propagates_to_all_waiters(self, checkpoints):
        from modelx_tpu.dl.serve import Batcher

        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32")
        server.load()
        batcher = Batcher(server, window_ms=50)

        def boom(tokens):
            raise RuntimeError("device fell over")

        server.forward_argmax = boom
        try:
            with pytest.raises(RuntimeError, match="fell over"):
                batcher.forward_argmax(np.array([[1, 2]], np.int32))
        finally:
            batcher.close()

    def test_http_route_uses_batcher(self, checkpoints):
        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32", name="g")
        sset = ServerSet({"g": server}, dynamic_batch=True)
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            r = requests.post(base + "/v1/forward", json={"tokens": [[1, 2, 3]]})
            assert r.status_code == 200
            assert sset.batchers["g"].batches >= 1
        finally:
            httpd.shutdown()

    def test_encoder_family_never_batched(self, checkpoints):
        """BERT is bidirectional: right-padding changes its outputs, so no
        batcher is created for encoder families even with dynamic_batch."""
        server = ModelServer(checkpoints["bert"], mesh_spec="dp=1", dtype="float32", name="b")
        sset = ServerSet({"b": server}, dynamic_batch=True)
        sset.load_all()
        assert sset.batcher_for(server) is None

    def test_generate_zero_new_tokens_returns_prompt(self, checkpoints):
        server = ModelServer(checkpoints["mixtral"], mesh_spec="dp=1", dtype="float32")
        server.load()
        out = server.generate(np.array([[4, 2]], np.int32), max_new_tokens=0)
        np.testing.assert_array_equal(out, [[4, 2]])

    def test_requests_after_close_fail_fast(self, checkpoints):
        from modelx_tpu.dl.serve import Batcher

        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32")
        server.load()
        batcher = Batcher(server, window_ms=50)
        batcher.close()
        with pytest.raises(RuntimeError, match="closed"):
            batcher.forward_argmax(np.array([[1]], np.int32))

    def test_1d_tokens_rejected_per_request(self, checkpoints):
        """Malformed input must 400 its own request, never poison a group."""
        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32", name="g")
        sset = ServerSet({"g": server}, dynamic_batch=True)
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            r = requests.post(base + "/v1/forward", json={"tokens": [1, 2, 3]})
            assert r.status_code == 400
            r = requests.post(base + "/v1/forward", json={"tokens": [[1, 2, 3]]})
            assert r.status_code == 200
        finally:
            httpd.shutdown()


class TestAOTWarmup:
    def test_warmup_shape_uses_aot_and_matches_jit(self, checkpoints):
        """load() precompiles the batcher's first-request shape on a side
        thread; the AOT executable must exist and agree bit-for-bit with the
        lazily-jitted forward path."""
        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32")
        server.load()
        shape = ModelServer.WARMUP_TOKEN_SHAPES[0]
        assert shape in server._forward_aot
        tokens = np.arange(shape[0] * shape[1], dtype=np.int32).reshape(shape) % 60 + 1
        via_aot = server.forward_argmax(tokens)
        # off-warmup shape exercises the jit path; slice back to compare
        del server._forward_aot[shape]
        via_jit = server.forward_argmax(tokens)
        np.testing.assert_array_equal(via_aot, via_jit)

    def test_quantized_load_precompiles_and_matches_jit(self, checkpoints):
        """int8 deploys overlap load+compile too: abstract_params mirrors the
        loader's QTensor transform, so the warmup AOT executable exists and
        agrees with the lazily-jitted quantized forward."""
        server = ModelServer(
            checkpoints["llama"], mesh_spec="dp=1", dtype="float32", quantize="int8"
        )
        server.load()
        shape = ModelServer.WARMUP_TOKEN_SHAPES[0]
        assert shape in server._forward_aot
        tokens = np.arange(shape[0] * shape[1], dtype=np.int32).reshape(shape) % 60 + 1
        via_aot = server.forward_argmax(tokens)
        del server._forward_aot[shape]
        via_jit = server.forward_argmax(tokens)
        np.testing.assert_array_equal(via_aot, via_jit)

    def test_ready_seconds_reported(self, checkpoints):
        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32")
        stats = server.load()
        assert stats["ready_seconds"] >= stats["load_seconds"] > 0


class TestGenerateBatching:
    # ~9 s concurrency soak; the http/mixed-group batching tests stay
    @pytest.mark.slow
    def test_concurrent_ragged_generates_coalesce_and_match(self, checkpoints):
        """Concurrent generate requests of different prompt lengths and
        decode budgets coalesce into one ragged device call and return
        exactly their unbatched results."""
        import concurrent.futures

        from modelx_tpu.dl.serve import Batcher

        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32")
        server.load()
        reqs = [
            (np.array([[1, 2, 3]], np.int32), 4),
            (np.array([[9, 8, 7, 6, 5, 4, 3]], np.int32), 2),
            (np.array([[5, 5], [6, 6]], np.int32), 3),  # multi-row request
            (np.array([[11]], np.int32), 5),
        ]
        expected = [server.generate(t, max_new_tokens=n) for t, n in reqs]
        batcher = Batcher(server, window_ms=80)
        try:
            with concurrent.futures.ThreadPoolExecutor(len(reqs)) as pool:
                got = list(pool.map(lambda r: batcher.generate(*r[:1], max_new_tokens=r[1]), reqs))
            device_calls = batcher.batches
        finally:
            batcher.close()
        for (t, n), e, g in zip(reqs, expected, got):
            assert g.shape == (t.shape[0], t.shape[1] + n)
            np.testing.assert_array_equal(e, g)
        assert device_calls < len(reqs)  # actually coalesced

    def test_mixed_forward_and_generate_group(self, checkpoints):
        import concurrent.futures

        from modelx_tpu.dl.serve import Batcher

        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32")
        server.load()
        fwd_tokens = np.array([[4, 5, 6]], np.int32)
        gen_tokens = np.array([[7, 8]], np.int32)
        want_fwd = server.forward_argmax(fwd_tokens)
        want_gen = server.generate(gen_tokens, max_new_tokens=3)
        batcher = Batcher(server, window_ms=80)
        try:
            with concurrent.futures.ThreadPoolExecutor(2) as pool:
                f1 = pool.submit(batcher.forward_argmax, fwd_tokens)
                f2 = pool.submit(batcher.generate, gen_tokens, 3)
                np.testing.assert_array_equal(want_fwd, f1.result())
                np.testing.assert_array_equal(want_gen, f2.result())
        finally:
            batcher.close()

    def test_http_generate_route_batches(self, checkpoints):
        """Through the real HTTP front with dynamic batching on, concurrent
        generate requests still return per-request results."""
        import concurrent.futures

        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32", name="g")
        sset = ServerSet({"g": server}, dynamic_batch=True)
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            want = {
                n: server.generate(np.array([[1, 2, n]], np.int32), max_new_tokens=4).tolist()
                for n in (3, 4, 5)
            }
            def call(n):
                r = requests.post(
                    base + "/v1/generate",
                    json={"tokens": [[1, 2, n]], "max_new_tokens": 4},
                )
                assert r.status_code == 200, r.text
                return n, r.json()["tokens"]
            with concurrent.futures.ThreadPoolExecutor(3) as pool:
                for n, got in pool.map(call, (3, 4, 5)):
                    assert got == want[n], n
        finally:
            httpd.shutdown()

    def test_empty_prompt_is_400(self, checkpoints):
        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32", name="e")
        sset = ServerSet({"e": server}, dynamic_batch=True)
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            for path in ("/v1/generate", "/v1/forward"):
                r = requests.post(base + path, json={"tokens": [[]]})
                assert r.status_code == 400, (path, r.text)
        finally:
            httpd.shutdown()

    @pytest.mark.slow  # tier-1 wall: the batching route test stays tier-1
    def test_tokens_generated_counts_requested_only(self, checkpoints):
        """Padded rows and the power-of-two decode bucket must not inflate
        the tokens_generated metric."""
        import concurrent.futures

        from modelx_tpu.dl.serve import Batcher

        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32")
        server.load()
        server.stats["tokens_generated"] = 0
        batcher = Batcher(server, window_ms=80)
        try:
            reqs = [(np.array([[1, 2]], np.int32), 3)] * 3  # 3 rows pad to 4
            with concurrent.futures.ThreadPoolExecutor(3) as pool:
                list(pool.map(lambda r: batcher.generate(r[0], r[1]), reqs))
        finally:
            batcher.close()
        assert server.stats["tokens_generated"] == 9

    def test_sampling_params_over_http(self, checkpoints):
        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32", name="s")
        sset = ServerSet({"s": server}, dynamic_batch=True)
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            body = {"tokens": [[1, 2, 3]], "max_new_tokens": 5,
                    "temperature": 0.8, "seed": 11}
            a = requests.post(base + "/v1/generate", json=body)
            b = requests.post(base + "/v1/generate", json=body)
            assert a.status_code == b.status_code == 200
            assert a.json() == b.json()  # same seed -> deterministic
            # validation
            for bad in ({"temperature": -1}, {"top_p": 0}, {"top_p": 1.5},
                        {"top_k": -2}, {"temperature": "hot"}):
                r = requests.post(base + "/v1/generate",
                                  json={"tokens": [[1]], **bad})
                assert r.status_code == 400, bad
        finally:
            httpd.shutdown()


class TestStreamingGenerate:
    @pytest.mark.slow  # tier-1 wall: stream byte-equality also held by router/openai suites
    def test_stream_chunks_equal_nonstreamed(self, checkpoints):
        """Concatenated stream chunks must reproduce the one-shot result
        exactly, greedy and sampled, including a partial last chunk."""
        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32")
        server.load()
        tokens = np.array([[1, 2, 3]], np.int32)
        for kw in ({}, {"temperature": 0.9, "seed": 4}):
            n = 11  # not a multiple of chunk_size -> partial final chunk
            chunks = list(server.generate_stream(tokens, max_new_tokens=n,
                                                 chunk_size=4, **kw))
            assert [c.shape[1] for c in chunks] == [4, 4, 3]
            streamed = np.concatenate(chunks, axis=1)
            whole = server.generate(tokens, max_new_tokens=n, **kw)
            np.testing.assert_array_equal(streamed, whole[:, 3:], err_msg=str(kw))

    def test_http_stream_route(self, checkpoints):
        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32", name="st")
        sset = ServerSet({"st": server})
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            body = {"tokens": [[1, 2, 3]], "max_new_tokens": 10, "stream": True}
            with requests.post(base + "/v1/generate", json=body, stream=True) as r:
                assert r.status_code == 200
                assert r.headers["Content-Type"] == "application/x-ndjson"
                lines = [json.loads(ln) for ln in r.iter_lines() if ln]
            assert lines[-1] == {"done": True}
            streamed = [t for ln in lines[:-1] for t in ln["tokens"][0]]
            assert len(streamed) == 10
            whole = requests.post(
                base + "/v1/generate", json={"tokens": [[1, 2, 3]], "max_new_tokens": 10}
            ).json()["tokens"][0]
            assert streamed == whole[3:]
        finally:
            httpd.shutdown()

    def test_gpt2_streams_like_llama(self, checkpoints):
        """GPT-2 now exposes decode_fns: the streaming path must serve it
        and concatenate to the non-streamed result, same as llama."""
        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32", name="g")
        sset = ServerSet({"g": server})
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            body = {"tokens": [[7, 8, 9]], "max_new_tokens": 6, "stream": True}
            with requests.post(base + "/v1/g/generate", json=body, stream=True) as r:
                assert r.status_code == 200
                lines = [json.loads(ln) for ln in r.iter_lines() if ln]
            streamed = [t for ln in lines[:-1] for t in ln["tokens"][0]]
            whole = requests.post(
                base + "/v1/g/generate", json={"tokens": [[7, 8, 9]], "max_new_tokens": 6}
            ).json()["tokens"][0]
            assert streamed == whole[3:]
        finally:
            httpd.shutdown()

    def test_stream_unsupported_family_is_400(self, checkpoints):
        server = ModelServer(checkpoints["bert"], mesh_spec="dp=1", dtype="float32", name="b")
        sset = ServerSet({"b": server})
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            r = requests.post(base + "/v1/b/generate",
                              json={"tokens": [[1]], "stream": True})
            assert r.status_code == 400
        finally:
            httpd.shutdown()


class TestTextAPI:
    @pytest.fixture
    def text_front(self, checkpoints, tmp_path_factory):
        """Llama checkpoint with a tiny word-level tokenizer.json beside it."""
        tokenizers = pytest.importorskip("tokenizers")
        import shutil

        d = tmp_path_factory.mktemp("textmodel")
        shutil.copy(checkpoints["llama"] + "/model.safetensors", d / "model.safetensors")
        vocab = {"<unk>": 0, "hello": 1, "world": 2, "tpu": 3}
        vocab.update({f"w{i}": i for i in range(4, 64)})
        tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(vocab, unk_token="<unk>"))
        tok.pre_tokenizer = tokenizers.pre_tokenizers.Whitespace()
        tok.save(str(d / "tokenizer.json"))
        server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", name="t")
        sset = ServerSet({"t": server})
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        sset.load_all()
        yield base, server
        httpd.shutdown()

    def test_text_in_text_out(self, text_front):
        base, server = text_front
        r = requests.post(base + "/v1/generate",
                          json={"text": "hello world tpu", "max_new_tokens": 4})
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["tokens"][0][:3] == [1, 2, 3]  # encoded prompt
        assert len(body["tokens"][0]) == 7
        assert isinstance(body["text"], str)
        # decoded text equals decoding the generated ids ourselves
        want = server.tokenizer().decode(body["tokens"][0][3:])
        assert body["text"] == want

    def test_text_without_tokenizer_is_400(self, checkpoints):
        server = ModelServer(checkpoints["llama"], mesh_spec="dp=1", dtype="float32", name="nt")
        sset = ServerSet({"nt": server})
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            r = requests.post(base + "/v1/generate", json={"text": "hi"})
            assert r.status_code == 400
            assert "tokenizer" in r.json()["error"]
        finally:
            httpd.shutdown()

    def test_bad_text_types_are_400(self, text_front):
        base, _ = text_front
        for bad in ("", 7, ["a", "b"]):
            r = requests.post(base + "/v1/generate", json={"text": bad})
            assert r.status_code == 400, bad

    def test_text_with_stream_is_400(self, text_front):
        base, _ = text_front
        r = requests.post(base + "/v1/generate",
                          json={"text": "hello", "stream": True})
        assert r.status_code == 400
        assert "stream" in r.json()["error"]

    def test_text_on_forward_is_400(self, text_front):
        """text is a generate-only contract (docs/api.md): a typo'd verb
        must 400, not return an undocumented ids-only hybrid response."""
        base, _ = text_front
        r = requests.post(base + "/v1/forward", json={"text": "hello"})
        assert r.status_code == 400
        assert "generate" in r.json()["error"]


class TestGPT2PositionBound:
    """Decode past gpt2's n_positions silently clamps the wpe
    gather inside jit; both the cache constructor and the serving layer
    must refuse instead."""

    def test_decode_entry_points_refuse_past_n_positions(self):
        """The bound is on positions USED (prompt + max_new), not cache
        capacity: bucketed paths deliberately over-allocate cache."""
        import jax as _jax

        from modelx_tpu.models import gpt2

        cfg = gpt2.GPT2Config.tiny()  # n_positions=64
        params = gpt2.init_params(cfg, _jax.random.PRNGKey(0))
        prompt = np.ones((1, 60), np.int32)
        with pytest.raises(ValueError, match="position context"):
            FAMILIES["gpt2"].generate(params, prompt, cfg, max_new_tokens=5)
        with pytest.raises(ValueError, match="position context"):
            FAMILIES["gpt2"].generate_ragged(
                params, prompt, np.asarray([60], np.int32), cfg, max_new_tokens=5
            )
        # over-allocated cache alone is fine (bucketing does this)
        gpt2.init_kv_cache(cfg, 1, cfg.n_positions + 8)

    def test_serving_400s_past_context(self, checkpoints):
        server = ModelServer(checkpoints["gpt2"], mesh_spec="dp=1", dtype="float32", name="g")
        sset = ServerSet({"g": server})
        base = f"http://127.0.0.1:{free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        try:
            sset.load_all()
            n_pos = server.cfg.n_positions
            r = requests.post(base + "/v1/generate", json={
                "tokens": [[1] * 10], "max_new_tokens": n_pos})
            assert r.status_code == 400 and "context" in r.json()["error"]
            r = requests.post(base + "/v1/forward", json={"tokens": [[1] * (n_pos + 1)]})
            assert r.status_code == 400 and "context" in r.json()["error"]
            r = requests.post(base + "/v1/generate", json={
                "tokens": [[1, 2, 3]], "max_new_tokens": 4})
            assert r.status_code == 200
        finally:
            httpd.shutdown()
