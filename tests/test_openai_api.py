"""OpenAI-compatible surface (dl/openai_api.py): /v1/completions,
/v1/chat/completions (+SSE streaming), OpenAI-shape /v1/models — so stock
OpenAI SDK clients can point at the sidecar unchanged."""

import dataclasses
import json

import numpy as np
import pytest
import requests

import jax
import jax.numpy as jnp

from modelx_tpu.dl.openai_api import apply_stop, render_messages, APIError
from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
from modelx_tpu.registry.server import free_port


@pytest.fixture(scope="module")
def front(tmp_path_factory):
    """Tiny llama with a word-level tokenizer.json, served over HTTP."""
    tokenizers = pytest.importorskip("tokenizers")
    from modelx_tpu.dl import safetensors as st
    from modelx_tpu.models import llama

    d = tmp_path_factory.mktemp("oai")
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    st.write_safetensors(
        str(d / "model.safetensors"),
        {k: np.asarray(v) for k, v in llama.init_params(cfg, jax.random.PRNGKey(0)).items()},
    )
    vocab = {"<unk>": 0, "hello": 1, "world": 2, "tpu": 3}
    vocab.update({f"w{i}": i for i in range(4, 64)})
    tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = tokenizers.pre_tokenizers.Whitespace()
    tok.save(str(d / "tokenizer.json"))
    server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", name="m")
    sset = ServerSet({"m": server})
    base = f"http://127.0.0.1:{free_port()}"
    httpd = serve(sset, listen=base.rsplit("//", 1)[1])
    sset.load_all()
    yield base, server
    httpd.shutdown()


class TestCompletions:
    def test_completion_roundtrip_and_usage(self, front):
        base, server = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello world tpu", "max_tokens": 4,
                                "temperature": 0})
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["object"] == "text_completion"
        assert body["model"] == "m"
        assert body["id"].startswith("cmpl-")
        (choice,) = body["choices"]
        assert choice["finish_reason"] == "length"
        assert body["usage"] == {"prompt_tokens": 3, "completion_tokens": 4,
                                 "total_tokens": 7}
        # text equals decoding a direct token-id generate of the same prompt
        ids = server.tokenizer().encode("hello world tpu")
        out = server.generate(np.asarray([ids], np.int32), max_new_tokens=4)
        assert choice["text"] == server.tokenizer().decode(out[0, 3:].tolist())

    def test_batch_prompts_get_indexed_choices(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": ["hello world", "tpu hello"],
                                "max_tokens": 2, "temperature": 0})
        assert r.status_code == 200, r.text
        body = r.json()
        assert [c["index"] for c in body["choices"]] == [0, 1]
        assert body["usage"]["prompt_tokens"] == 4
        assert body["usage"]["completion_tokens"] == 4

    # ~6 s; single-prompt + n>1 paths keep the veneer covered in tier-1
    @pytest.mark.slow
    def test_batch_prompts_through_dynamic_batcher(self, front):
        """List prompts coalesce into one ragged decode via the batcher and
        match the unbatched engine's rows exactly."""
        _, server = front
        plain = ServerSet({"m": server})
        batched = ServerSet({"m": server}, dynamic_batch=True)
        base_p = f"http://127.0.0.1:{free_port()}"
        base_b = f"http://127.0.0.1:{free_port()}"
        h1 = serve(plain, listen=base_p.rsplit("//", 1)[1])
        h2 = serve(batched, listen=base_b.rsplit("//", 1)[1])
        try:
            req = {"prompt": ["hello world", "tpu hello world w9"],
                   "max_tokens": 3, "temperature": 0}
            a = requests.post(base_p + "/v1/completions", json=req).json()
            b = requests.post(base_b + "/v1/completions", json=req).json()
            assert [c["text"] for c in a["choices"]] == [c["text"] for c in b["choices"]]
            assert a["usage"] == b["usage"]
        finally:
            h1.shutdown()
            h2.shutdown()
            for batcher in batched.batchers.values():
                batcher.close()

    def test_default_model_and_explicit_model(self, front):
        base, _ = front
        for req in ({"prompt": "hello"}, {"prompt": "hello", "model": "m"}):
            r = requests.post(base + "/v1/completions", json={**req, "max_tokens": 1})
            assert r.status_code == 200, r.text
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello", "model": "nope"})
        assert r.status_code == 404
        assert r.json()["error"]["type"] == "not_found_error"

    def test_validation_errors_are_openai_shaped(self, front):
        base, _ = front
        cases = [
            {"prompt": ""},
            {"prompt": 7},
            {"prompt": "hello", "max_tokens": 0},
            {"prompt": "hello", "temperature": 3.0},
            {"prompt": "hello", "top_p": 0.0},
            {"prompt": "hello", "stop": ["a", "b", "c", "d", "e"]},
            {"prompt": "hello", "n": 0},  # n itself is supported now
            {"prompt": "hello", "logprobs": 6},  # > the completions cap
            {"prompt": "hello", "logprobs": True},  # bool is the CHAT form
            {"prompt": ["hello"] * 33},  # prompt-list cap
        ]
        for req in cases:
            r = requests.post(base + "/v1/completions", json=req)
            assert r.status_code == 400, req
            err = r.json()["error"]
            assert err["type"] == "invalid_request_error" and err["message"], req

    def test_stop_sequence_truncates(self, front):
        base, server = front
        # find what greedy decoding emits, then use its first word as stop
        tok = server.tokenizer()
        ids = tok.encode("hello world tpu")
        out = server.generate(np.asarray([ids], np.int32), max_new_tokens=4)
        first_word = tok.decode(out[0, 3:4].tolist())
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello world tpu", "max_tokens": 4,
                                "temperature": 0, "stop": [first_word]})
        assert r.status_code == 200, r.text
        (choice,) = r.json()["choices"]
        assert choice["finish_reason"] == "stop"
        assert first_word not in choice["text"]

    def test_models_serves_both_contracts(self, front):
        base, _ = front
        body = requests.get(base + "/v1/models").json()
        assert body["object"] == "list"
        assert [m["id"] for m in body["data"]] == ["m"]
        assert body["default"] == "m" and body["models"]["m"]["ready"]


class TestChat:
    def test_chat_roundtrip(self, front):
        base, _ = front
        r = requests.post(base + "/v1/chat/completions",
                          json={"messages": [
                                    {"role": "system", "content": "hello"},
                                    {"role": "user", "content": "world tpu"},
                                ],
                                "max_tokens": 3, "temperature": 0})
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["object"] == "chat.completion"
        (choice,) = body["choices"]
        assert choice["message"]["role"] == "assistant"
        assert isinstance(choice["message"]["content"], str)
        assert choice["finish_reason"] == "length"

    def test_message_validation(self, front):
        base, _ = front
        for messages in ([], [{"role": "alien", "content": "x"}],
                         [{"role": "user"}], "hi"):
            r = requests.post(base + "/v1/chat/completions",
                              json={"messages": messages})
            assert r.status_code == 400, messages

    def test_render_template_is_stable(self):
        text = render_messages([
            {"role": "system", "content": "s"},
            {"role": "user", "content": "u"},
        ])
        assert text == "<|system|>\ns\n<|user|>\nu\n<|assistant|>\n"


class TestTemplateFailureTriage:
    """Template render failures split by blame: message-dependent renders
    stay 400 (the caller's payload), while a template that ALSO fails on a
    trivial probe is a server-side defect — the request falls back to the
    generic role template instead of bouncing with a misleading 400."""

    def _spec(self, render_fn):
        class _Fake:
            def render(self, messages, **_kw):
                return render_fn(messages)

        return {"compiled": _Fake(), "bos_token": "", "eos_token": ""}

    def test_message_dependent_failure_is_400(self):
        def render(messages):
            if any("boom" in m["content"] for m in messages):
                raise RuntimeError("cannot format this content")
            return "rendered"

        spec = self._spec(render)
        with pytest.raises(APIError) as ei:
            render_messages([{"role": "user", "content": "boom"}], spec)
        assert ei.value.status == 400
        # the same template still serves well-formed payloads
        assert render_messages([{"role": "user", "content": "ok"}], spec) == "rendered"

    def test_broken_template_falls_back_to_generic(self):
        calls = []

        def render(_messages):
            calls.append(1)
            raise RuntimeError("no filter named 'tojson'")  # payload-independent

        spec = self._spec(render)
        text = render_messages([{"role": "user", "content": "u"}], spec)
        assert text == "<|user|>\nu\n<|assistant|>\n"
        assert len(calls) == 2  # the real render + the probe
        # the broken verdict memoizes per model: later requests go straight
        # to the generic template, no re-render / re-probe / re-warn
        text2 = render_messages([{"role": "user", "content": "v"}], spec)
        assert text2 == "<|user|>\nv\n<|assistant|>\n"
        assert len(calls) == 2

    def test_probe_rejection_does_not_mark_template_broken(self):
        """A template whose raise_exception fires on the bare probe (e.g.
        it requires a system turn) is template logic working, not breakage:
        the original failure stays a 400 and later well-formed requests
        still get the real template."""
        from modelx_tpu.dl.serve import ChatTemplateRejected

        def render(messages):
            if not any(m["role"] == "system" for m in messages):
                raise ChatTemplateRejected("needs a system turn")
            if "boom" in messages[-1]["content"]:
                raise RuntimeError("message-dependent failure")
            return "rendered"

        spec = self._spec(render)
        with pytest.raises(APIError) as ei:
            render_messages([
                {"role": "system", "content": "s"},
                {"role": "user", "content": "boom"},
            ], spec)
        assert ei.value.status == 400
        assert not spec.get("broken")
        assert render_messages([
            {"role": "system", "content": "s"},
            {"role": "user", "content": "ok"},
        ], spec) == "rendered"


@pytest.fixture(scope="module")
def templated_front(tmp_path_factory):
    """Like ``front`` but the model SHIPS a chat_template (the HF
    tokenizer_config.json convention): 'hello <contents...> world', with
    bos_token in AddedToken form — rendered prompts have exactly known
    token ids, so the tests can prove the template (not the generic
    fallback) produced the prompt."""
    import json as _json

    tokenizers = pytest.importorskip("tokenizers")
    from modelx_tpu.dl import safetensors as st
    from modelx_tpu.models import llama

    d = tmp_path_factory.mktemp("oai-tpl")
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    st.write_safetensors(
        str(d / "model.safetensors"),
        {k: np.asarray(v) for k, v in llama.init_params(cfg, jax.random.PRNGKey(0)).items()},
    )
    vocab = {"<unk>": 0, "hello": 1, "world": 2, "tpu": 3}
    vocab.update({f"w{i}": i for i in range(4, 64)})
    tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = tokenizers.pre_tokenizers.Whitespace()
    tok.save(str(d / "tokenizer.json"))
    (d / "tokenizer_config.json").write_text(_json.dumps({
        "bos_token": {"content": "hello"},  # AddedToken form
        "chat_template": (
            "{{ bos_token }} "
            "{% for m in messages %}"
            "{% if m['role'] not in ['system', 'user', 'assistant'] %}"
            "{{ raise_exception('unknown role ' + m['role']) }}"
            "{% endif %}"
            "{{ m['content'] }} "
            "{% endfor %}"
            "{% if add_generation_prompt %}world{% endif %}"
        ),
    }))
    server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", name="t")
    sset = ServerSet({"t": server})
    base = f"http://127.0.0.1:{free_port()}"
    httpd = serve(sset, listen=base.rsplit("//", 1)[1])
    sset.load_all()
    yield base, server
    httpd.shutdown()


class TestChatTemplate:
    def test_model_template_drives_the_prompt(self, templated_front):
        """messages {content: tpu} must render 'hello tpu world' = ids
        [1, 3, 2] — prompt_tokens 3 proves the model template ran (the
        generic fallback renders role markers that tokenize differently)
        and that encoding skipped add_special_tokens (HF convention)."""
        base, server = templated_front
        r = requests.post(base + "/v1/chat/completions",
                          json={"messages": [{"role": "user", "content": "tpu"}],
                                "max_tokens": 2, "temperature": 0})
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["usage"]["prompt_tokens"] == 3
        # and the completion equals decoding the exact-token generate
        out = server.generate(np.asarray([[1, 3, 2]], np.int32), max_new_tokens=2)
        want = server.tokenizer().decode(out[0, 3:].tolist())
        assert body["choices"][0]["message"]["content"] == want

    def test_template_raise_exception_is_400(self, templated_front):
        base, _ = templated_front
        r = requests.post(base + "/v1/chat/completions",
                          json={"messages": [{"role": "tool", "content": "x"}],
                                "max_tokens": 2})
        assert r.status_code == 400
        assert "unknown role tool" in r.json()["error"]["message"]

    def test_streaming_uses_the_template_too(self, templated_front):
        base, server = templated_front
        r = requests.post(base + "/v1/chat/completions",
                          json={"messages": [{"role": "user", "content": "tpu"}],
                                "max_tokens": 3, "temperature": 0,
                                "stream": True,
                                "stream_options": {"include_usage": True}},
                          stream=True)
        assert r.status_code == 200, r.text
        usage = None
        for line in r.iter_lines():
            if not line or not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                break
            evt = json.loads(payload)
            if evt.get("usage"):
                usage = evt["usage"]
        assert usage and usage["prompt_tokens"] == 3

    def test_completions_route_ignores_chat_template(self, templated_front):
        """Plain /v1/completions must NOT run the chat template."""
        base, _ = templated_front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "tpu", "max_tokens": 1,
                                "temperature": 0})
        assert r.status_code == 200, r.text
        assert r.json()["usage"]["prompt_tokens"] == 1

    def test_chat_template_parsing_forms(self, tmp_path):
        """ModelServer.chat_template: string form, named-list form,
        AddedToken vs string specials, broken JSON -> None, and the
        compiled template renders with the HF conveniences."""
        import json as _json
        import threading as _threading

        from modelx_tpu.dl.serve import ModelServer, _UNSET

        d = str(tmp_path)
        srv = ModelServer.__new__(ModelServer)
        srv.model_dir = d
        srv._tokenizer_lock = _threading.Lock()

        def reset():
            srv._chat_template = _UNSET

        # string form + string bos; compiled once and render-ready
        (tmp_path / "tokenizer_config.json").write_text(_json.dumps({
            "chat_template": "{{ bos_token }}{{ messages[0]['content'] }}",
            "bos_token": "<s>", "eos_token": {"content": "</s>"},
        }))
        reset()
        spec = srv.chat_template()
        assert spec["bos_token"] == "<s>" and spec["eos_token"] == "</s>"
        out = spec["compiled"].render(messages=[{"content": "x"}],
                                      add_generation_prompt=True,
                                      bos_token="<s>", eos_token="")
        assert out == "<s>x"
        # the compiled object is cached (no re-parse per request)
        assert srv.chat_template()["compiled"] is spec["compiled"]
        # HF conveniences: strftime_now + loop controls compile and run
        (tmp_path / "tokenizer_config.json").write_text(_json.dumps({
            "chat_template": (
                "{{ strftime_now('%Y') }}"
                "{% for m in messages %}{% if loop.index > 1 %}{% break %}"
                "{% endif %}{{ m['content'] }}{% endfor %}"
            ),
        }))
        reset()
        out = srv.chat_template()["compiled"].render(
            messages=[{"content": "a"}, {"content": "b"}],
            add_generation_prompt=True, bos_token="", eos_token="")
        assert out.endswith("a") and not out.endswith("ab")
        assert len(out) == 5  # 4-digit year + "a"
        # named-list form picks "default" ONLY
        (tmp_path / "tokenizer_config.json").write_text(_json.dumps({
            "chat_template": [
                {"name": "tool_use", "template": "T"},
                {"name": "default", "template": "D"},
            ],
        }))
        reset()
        assert srv.chat_template()["template"] == "D"
        # named-list WITHOUT default -> None (never silently pick tool_use)
        (tmp_path / "tokenizer_config.json").write_text(_json.dumps({
            "chat_template": [{"name": "tool_use", "template": "T"}],
        }))
        reset()
        assert srv.chat_template() is None
        # broken json -> None (generic fallback), not an exception
        (tmp_path / "tokenizer_config.json").write_text("{broken")
        reset()
        assert srv.chat_template() is None
        # absent file -> None
        (tmp_path / "tokenizer_config.json").unlink()
        reset()
        assert srv.chat_template() is None


class TestStreaming:
    def _events(self, resp):
        assert resp.headers["Content-Type"] == "text/event-stream"
        raw = resp.content.decode()
        assert raw.endswith("data: [DONE]\n\n")
        return [json.loads(line[len("data: "):])
                for line in raw.split("\n\n")
                if line.startswith("data: ") and line != "data: [DONE]"]

    def test_stream_concatenates_to_nonstreamed(self, front):
        base, _ = front
        req = {"prompt": "hello world tpu", "max_tokens": 6, "temperature": 0}
        plain = requests.post(base + "/v1/completions", json=req).json()
        r = requests.post(base + "/v1/completions", json={**req, "stream": True})
        assert r.status_code == 200, r.text
        events = self._events(r)
        text = "".join(c["text"] for e in events for c in e["choices"])
        assert text == plain["choices"][0]["text"]
        assert events[-1]["choices"][0]["finish_reason"] == "length"

    def test_chat_stream_role_then_deltas(self, front):
        base, _ = front
        r = requests.post(base + "/v1/chat/completions",
                          json={"messages": [{"role": "user", "content": "hello world"}],
                                "max_tokens": 4, "temperature": 0, "stream": True})
        assert r.status_code == 200, r.text
        events = self._events(r)
        assert events[0]["object"] == "chat.completion.chunk"
        assert events[0]["choices"][0]["delta"] == {"role": "assistant"}
        assert events[-1]["choices"][0]["finish_reason"] in ("length", "stop")
        content = "".join(e["choices"][0]["delta"].get("content", "")
                          for e in events[1:])
        assert isinstance(content, str)

    def test_stream_include_usage(self, front):
        base, _ = front
        req = {"prompt": "hello world tpu", "max_tokens": 4, "temperature": 0,
               "stream": True, "stream_options": {"include_usage": True}}
        r = requests.post(base + "/v1/completions", json=req)
        assert r.status_code == 200, r.text
        events = self._events(r)
        usage_events = [e for e in events if "usage" in e]
        assert len(usage_events) == 1
        assert usage_events[-1] is events[-1] and events[-1]["choices"] == []
        assert events[-1]["usage"] == {"prompt_tokens": 3, "completion_tokens": 4,
                                       "total_tokens": 7}
        # invalid stream_options is a 400, not a silent ignore
        r = requests.post(base + "/v1/completions",
                          json={**req, "stream_options": 7})
        assert r.status_code == 400
        # and stream_options without stream=true is a 400 (OpenAI contract)
        r = requests.post(base + "/v1/completions",
                          json={**req, "stream": False})
        assert r.status_code == 400
        assert "stream" in r.json()["error"]["message"]

    def test_stream_usage_carries_timing_block(self, front):
        """ISSUE 13: on an engine with phase machinery (the continuous
        batcher), the opt-in final usage chunk also carries the
        per-request timing breakdown; the default stream (no
        include_usage) stays byte-unchanged."""
        _, server = front
        sset = ServerSet({"m": server}, continuous_batch=True, max_slots=2,
                         stream_chunk_size=4)
        sset.pool.mark_ready("m")
        httpd = serve(sset, listen=f"127.0.0.1:{free_port()}")
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            req = {"prompt": "hello world tpu", "max_tokens": 6,
                   "temperature": 0, "stream": True,
                   "stream_options": {"include_usage": True}}
            r = requests.post(base + "/v1/completions", json=req)
            assert r.status_code == 200, r.text
            events = self._events(r)
            assert "usage" in events[-1]
            timing = events[-1].get("timing")
            assert timing, events[-1]
            assert timing["ttft_ms"] > 0
            assert timing.get("queue_ms", 0) >= 0
            # without include_usage no timing (or usage) chunk appears
            r = requests.post(base + "/v1/completions",
                              json={"prompt": "hello world tpu",
                                    "max_tokens": 6, "temperature": 0,
                                    "stream": True})
            assert not any("timing" in e or "usage" in e
                           for e in self._events(r))
        finally:
            httpd.shutdown()
            for cb in sset.cbatchers.values():
                cb.close()
                cb.release_device_state()

    def test_stream_validation_is_pre_status(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "", "stream": True})
        assert r.status_code == 400
        r = requests.post(base + "/v1/completions",
                          json={"prompt": ["a", "b"], "stream": True})
        assert r.status_code == 400  # stream supports a single prompt


class TestStopStraddle:
    """A stop sequence split across decode chunks must never leak text past
    the match into the stream (stream == non-stream contract)."""

    def _fake_sset(self, pieces):
        from types import SimpleNamespace

        class Tok:
            def encode(self, text, add_special_tokens=True):
                return [1, 2]

            def decode(self, ids):
                return " ".join(f"w{i}" for i in ids)

        import types as _types

        from modelx_tpu.dl.serve import ServerSet

        server = SimpleNamespace(
            name="f", ready=True, speculative_k=0,
            chat_template=lambda: None,
            cfg=SimpleNamespace(vocab_size=100),
            family=SimpleNamespace(decode_fns=object(), name="fake",
                                   generate_ragged=None),
            stats={"requests": 0},
            tokenizer=lambda: Tok(),
            generate_stream=lambda tokens, max_new_tokens, **samp: (
                np.asarray(p) for p in pieces
            ),
        )
        sset = SimpleNamespace(servers={"f": server}, default="f",
                               max_new_tokens_limit=64, stream_chunk_size=8,
                               batcher_for=lambda s: None,
                               continuous_for=lambda s: None)
        # bind the REAL routing methods so the fake can't drift from the
        # policy the production ServerSet applies
        sset.stream_source = _types.MethodType(ServerSet.stream_source, sset)
        sset.engine_for = _types.MethodType(ServerSet.engine_for, sset)
        return sset

    def _stream_text(self, sset, stop):
        from modelx_tpu.dl.openai_api import stream_completion

        events = list(stream_completion(
            sset, {"prompt": "x", "max_tokens": 8, "stop": stop}, chat=False))
        assert events[-1]["choices"][0]["finish_reason"] == (
            "stop" if stop else "length")
        return "".join(c["text"] for e in events for c in e["choices"])

    def test_stop_spanning_two_chunks_emits_nothing_past_it(self):
        # chunks decode to "w5", then "w5 w6": stop "w5 w6" spans both
        sset = self._fake_sset([[[5]], [[6]], [[7]]])
        assert self._stream_text(sset, ["w5 w6"]) == ""

    def test_partial_stop_prefix_held_back_then_cut(self):
        sset = self._fake_sset([[[5]], [[6]], [[7]]])
        # stop "w6" first completes in chunk 2; text before it all emits
        assert self._stream_text(sset, ["w6"]) == "w5 "

    def test_no_stop_flushes_everything(self):
        sset = self._fake_sset([[[5]], [[6]]])
        assert self._stream_text(sset, []) == "w5 w6"

    def test_incomplete_glyph_held_back_until_resolved(self):
        """Byte-level BPE can split one glyph across chunks: the interim
        decode ends in U+FFFD, which must stay off the wire until the next
        chunk resolves it — streamed text equals the final decode."""
        decodes = {(5,): "a�", (5, 6): "aé", (5, 6, 7): "aéb"}
        sset = self._fake_sset([[[5]], [[6]], [[7]]])
        tok = sset.servers["f"].tokenizer()
        tok.decode = lambda ids: decodes[tuple(ids)]
        sset.servers["f"].tokenizer = lambda: tok
        assert self._stream_text(sset, []) == "aéb"


class TestHelpers:
    def test_apply_stop(self):
        assert apply_stop("a b c", ["b"]) == ("a ", "stop")
        assert apply_stop("a b c", ["z"]) == ("a b c", "length")
        assert apply_stop("a b c", ["c", "b"]) == ("a ", "stop")
        assert apply_stop("abc", []) == ("abc", "length")

    def test_api_error_payload_shape(self):
        e = APIError(400, "nope")
        assert e.payload["error"]["message"] == "nope"
        assert e.payload["error"]["type"] == "invalid_request_error"

    def test_seed_random_when_absent_fixed_when_given(self):
        """OpenAI semantics: no seed = nondeterministic; explicit seed pins
        the sample stream."""
        from modelx_tpu.dl.openai_api import parse_sampling

        _, a = parse_sampling({}, 1024)
        _, b = parse_sampling({}, 1024)
        assert a["seed"] != b["seed"]  # 2^-31 collision odds
        _, c = parse_sampling({"seed": 7}, 1024)
        assert c["seed"] == 7


class TestMaxCompletionTokens:
    """max_completion_tokens (the current OpenAI chat param) is
    an alias for max_tokens, preferred when both are present."""

    def _sampling(self, req):
        from modelx_tpu.dl.openai_api import parse_sampling

        return parse_sampling(req, 64)

    def test_alias_honored(self):
        n, _ = self._sampling({"max_completion_tokens": 33})
        assert n == 33

    def test_current_name_wins_over_deprecated(self):
        n, _ = self._sampling({"max_completion_tokens": 33, "max_tokens": 5})
        assert n == 33

    def test_null_falls_back(self):
        n, _ = self._sampling({"max_completion_tokens": None, "max_tokens": 5})
        assert n == 5

    def test_non_numeric_400(self):
        from modelx_tpu.dl.openai_api import APIError

        with pytest.raises(APIError):
            self._sampling({"max_completion_tokens": "many"})

    def test_limit_applies(self):
        from modelx_tpu.dl.openai_api import APIError

        with pytest.raises(APIError, match="max_completion_tokens"):
            self._sampling({"max_completion_tokens": 100000})


class TestContextBound:
    def test_encode_prompt_400s_past_n_positions(self):
        """gpt2-style absolute-position models: prompt + max_tokens past
        n_positions must 400 on the OpenAI path too."""
        from types import SimpleNamespace

        from modelx_tpu.dl.openai_api import encode_prompt

        class Tok:
            def encode(self, text, add_special_tokens=True):
                return list(range(1, 11))  # 10 tokens

        server = SimpleNamespace(cfg=SimpleNamespace(vocab_size=100, n_positions=16))
        assert encode_prompt(Tok(), server, "x", n_tokens=6)  # 10+6 = 16 fits
        with pytest.raises(APIError, match="position context"):
            encode_prompt(Tok(), server, "x", n_tokens=7)  # 17 > 16


class TestAutoEOS:
    """The OpenAI layer ends generation at the tokenizer's EOS: content
    excludes the EOS token, finish_reason is "stop", usage counts it, and
    ignore_eos (vLLM-compatible extension) opts out."""

    def test_eos_ids_discovered_from_vocab(self):
        tokenizers = pytest.importorskip("tokenizers")
        from modelx_tpu.dl.serve import _Tokenizer

        vocab = {"<unk>": 0, "hello": 1, "</s>": 2, "<|im_end|>": 3}
        tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(vocab, unk_token="<unk>"))
        t = _Tokenizer(tok)
        assert set(t.eos_ids()) == {2, 3}
        vocab2 = {"<unk>": 0, "hello": 1}
        tok2 = tokenizers.Tokenizer(tokenizers.models.WordLevel(vocab2, unk_token="<unk>"))
        assert _Tokenizer(tok2).eos_ids() == ()

    def test_eos_override_from_config_sidecars(self, tmp_path):
        """An explicit eos_token_id in the checkpoint's config sidecars
        beats the spelling probe (chatml-style vocabs carry probe
        spellings as NON-eos specials, e.g. <|endoftext|> as pad)."""
        tokenizers = pytest.importorskip("tokenizers")
        import json as _json
        import os as _os

        from modelx_tpu.dl.serve import _Tokenizer, _eos_from_config

        vocab = {"<unk>": 0, "<|endoftext|>": 1, "<|im_end|>": 2}
        tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(vocab, unk_token="<unk>"))
        d = str(tmp_path)
        assert _eos_from_config(d, tok) is None  # no sidecars: probe rules
        (tmp_path / "config.json").write_text(_json.dumps({"eos_token_id": 2}))
        assert _eos_from_config(d, tok) == (2,)
        # generation_config.json wins over config.json; int lists pass
        (tmp_path / "generation_config.json").write_text(
            _json.dumps({"eos_token_id": [2, 1]})
        )
        assert _eos_from_config(d, tok) == (2, 1)
        # tokenizer_config's eos spelling resolves through the vocab
        for f in ("config.json", "generation_config.json"):
            _os.unlink(tmp_path / f)
        (tmp_path / "tokenizer_config.json").write_text(
            _json.dumps({"eos_token": "<|im_end|>"})
        )
        assert _eos_from_config(d, tok) == (2,)
        # added-token object form
        (tmp_path / "tokenizer_config.json").write_text(
            _json.dumps({"eos_token": {"content": "<|im_end|>"}})
        )
        assert _eos_from_config(d, tok) == (2,)
        # malformed sidecars / bool ids never raise, fall through
        (tmp_path / "config.json").write_text("{broken")
        (tmp_path / "generation_config.json").write_text(
            _json.dumps({"eos_token_id": True})
        )
        assert _eos_from_config(d, tok) == (2,)
        # the override short-circuits the probe in the facade
        assert _Tokenizer(tok, eos_override=(2,)).eos_ids() == (2,)
        # WITHOUT an override, the probe on this vocab would say {1, 2} —
        # the chatml failure the override exists to prevent
        assert set(_Tokenizer(tok).eos_ids()) == {1, 2}

    def test_stream_divergent_final_flush_not_dropped(self):
        """When the final re-decode no longer extends the bytes already on
        the wire (split glyph before an EOS), the held-back remainder is
        emitted past the longest common prefix instead of silently dropped."""
        sset, _ = self._eos_sset([[[5]], [[6, 50]]], eos=(50,))
        server = sset.servers["f"]

        class DivergingTok:
            def encode(self, text, add_special_tokens=True):
                return [1, 2]

            def decode(self, ids):
                # one token decodes provisionally (trailing replacement
                # char); the full sequence re-decodes to different text
                if list(ids) == [5]:
                    return "a�"
                return "X rewritten"

            def eos_ids(self):
                return (50,)

        server.tokenizer = lambda: DivergingTok()
        text, finish, _ = self._collect(
            sset, {"prompt": "x", "max_tokens": 8})
        # "a" went out first (stable prefix); the divergent remainder must
        # still arrive — content ends with the re-decoded tail
        assert text.endswith("X rewritten")
        assert finish == ["stop"]

    def _eos_sset(self, pieces, eos=(50,)):
        """TestStopStraddle's fake harness, with an EOS-aware tokenizer."""
        from types import SimpleNamespace
        import types as _types

        from modelx_tpu.dl.serve import ServerSet

        class Tok:
            def encode(self, text, add_special_tokens=True):
                return [1, 2]

            def decode(self, ids):
                return " ".join(f"w{i}" for i in ids)

            def eos_ids(self):
                return tuple(eos)

        consumed = []

        def gen_stream(tokens, max_new_tokens, **samp):
            # the real engines stop AT the eos; mimic by ending the piece
            # stream there (and record what the layer asked for)
            consumed.append(samp.get("stop_token_ids"))
            import numpy as _np

            for p in pieces:
                yield _np.asarray(p)
                if any(t in eos for t in p[0]):
                    return

        server = SimpleNamespace(
            name="f", ready=True, speculative_k=0,
            chat_template=lambda: None,
            cfg=SimpleNamespace(vocab_size=100),
            family=SimpleNamespace(decode_fns=object(), name="fake",
                                   generate_ragged=None),
            stats={"requests": 0},
            tokenizer=lambda: Tok(),
            generate_stream=gen_stream,
        )
        sset = SimpleNamespace(servers={"f": server}, default="f",
                               max_new_tokens_limit=64, stream_chunk_size=8,
                               batcher_for=lambda s: None,
                               continuous_for=lambda s: None)
        sset.stream_source = _types.MethodType(ServerSet.stream_source, sset)
        sset.engine_for = _types.MethodType(ServerSet.engine_for, sset)
        return sset, consumed

    def _collect(self, sset, req):
        from modelx_tpu.dl.openai_api import stream_completion

        events = list(stream_completion(sset, req, chat=False))
        text = "".join(c.get("text", "") for e in events for c in e["choices"])
        finish = [c["finish_reason"] for e in events for c in e["choices"]
                  if c["finish_reason"]]
        usage = [e["usage"] for e in events if e.get("usage")]
        return text, finish, usage

    def test_stream_stops_at_eos_excluding_it(self):
        sset, consumed = self._eos_sset([[[5]], [[6, 50]], [[7]]])
        text, finish, usage = self._collect(
            sset, {"prompt": "x", "max_tokens": 8,
                   "stream_options": {"include_usage": True}})
        assert text == "w5 w6"  # no w50, no w7
        assert finish == ["stop"]
        assert usage[0]["completion_tokens"] == 3  # w5, w6, and the EOS
        assert consumed == [[50]]  # the engine was asked to stop there

    def test_ignore_eos_runs_full_budget(self):
        sset, consumed = self._eos_sset([[[5]], [[6, 50]], [[7]]])
        text, finish, _ = self._collect(
            sset, {"prompt": "x", "max_tokens": 8, "ignore_eos": True})
        # the fake engine still ends its piece stream, but the layer asked
        # for NO stop ids and keeps the eos token's text in the content
        assert consumed == [None]
        assert "w50" in text
        assert finish == ["length"]

    def test_ignore_eos_type_validated(self):
        sset, _ = self._eos_sset([[[5]]])
        from modelx_tpu.dl.openai_api import stream_completion

        with pytest.raises(APIError, match="ignore_eos"):
            list(stream_completion(sset, {"prompt": "x", "ignore_eos": "yes"},
                                   chat=False))

    def test_nonstream_trims_at_eos(self, front, tmp_path):
        """Full stack: serve a model whose tokenizer maps </s> to a token
        the greedy continuation actually produces; the completion must end
        there with finish_reason stop."""
        tokenizers = pytest.importorskip("tokenizers")
        import dataclasses

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer, ServerSet
        from modelx_tpu.dl.openai_api import run_completion
        from modelx_tpu.models import llama

        base, ref_server = front
        ids = ref_server.tokenizer().encode("hello world tpu")
        full = ref_server.generate(np.asarray([ids], np.int32),
                                   max_new_tokens=6)[0, len(ids):].tolist()
        eos_id = full[3]
        if eos_id < 4:
            pytest.skip("greedy continuation collides with reserved vocab ids")
        # same weights, but the tokenizer now names eos_id "</s>"
        d = ref_server.model_dir
        import shutil

        d2 = str(tmp_path)
        shutil.copy(d + "/model.safetensors", d2 + "/model.safetensors")
        vocab = {"<unk>": 0, "hello": 1, "world": 2, "tpu": 3}
        vocab.update({f"w{i}": i for i in range(4, 64) if i != eos_id})
        vocab["</s>"] = eos_id
        tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(vocab, unk_token="<unk>"))
        tok.pre_tokenizer = tokenizers.pre_tokenizers.Whitespace()
        tok.save(d2 + "/tokenizer.json")
        server = ModelServer(d2, mesh_spec="dp=1", dtype="float32", name="e")
        server.load()
        sset = ServerSet({"e": server})
        body = run_completion(sset, {"prompt": "hello world tpu",
                                     "max_tokens": 6, "temperature": 0}, chat=False)
        (choice,) = body["choices"]
        assert choice["finish_reason"] == "stop"
        assert "</s>" not in choice["text"]
        # content = the tokens before the eos
        expect = server.tokenizer().decode(full[:3])
        assert choice["text"] == expect
        assert body["usage"]["completion_tokens"] == 4  # 3 content + eos
        # ignore_eos: full budget, eos text present
        body2 = run_completion(sset, {"prompt": "hello world tpu", "max_tokens": 6,
                                      "temperature": 0, "ignore_eos": True}, chat=False)
        assert body2["choices"][0]["finish_reason"] == "length"
        assert "</s>" in body2["choices"][0]["text"]


class TestNAndLogprobs:
    """OpenAI n + logprobs (VERDICT r4 item 5): n rides the per-row-seed
    multi-row decode; logprobs come from one scoring forward over
    prompt+completion (ModelServer.score_logprobs)."""

    def test_n_greedy_returns_identical_choices(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello world tpu", "max_tokens": 4,
                                "temperature": 0, "n": 3})
        assert r.status_code == 200, r.text
        body = r.json()
        assert [c["index"] for c in body["choices"]] == [0, 1, 2]
        texts = [c["text"] for c in body["choices"]]
        assert texts[0] == texts[1] == texts[2]  # greedy: same stream
        assert body["usage"]["completion_tokens"] == 12  # 3 x 4

    def test_n_sampled_choices_use_distinct_streams(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello world tpu", "max_tokens": 8,
                                "temperature": 1.0, "seed": 7, "n": 4})
        assert r.status_code == 200, r.text
        texts = [c["text"] for c in r.json()["choices"]]
        assert len(texts) == 4
        assert len(set(texts)) > 1, "n samples came from one stream"
        # deterministic per request seed: same request, same set of samples
        r2 = requests.post(base + "/v1/completions",
                           json={"prompt": "hello world tpu", "max_tokens": 8,
                                 "temperature": 1.0, "seed": 7, "n": 4})
        assert [c["text"] for c in r2.json()["choices"]] == texts

    def test_completions_logprobs_shape_and_greedy_argmax(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello world tpu", "max_tokens": 5,
                                "temperature": 0, "logprobs": 3})
        assert r.status_code == 200, r.text
        (choice,) = r.json()["choices"]
        lp = choice["logprobs"]
        assert len(lp["tokens"]) == 5
        assert len(lp["token_logprobs"]) == 5
        assert len(lp["top_logprobs"]) == 5
        assert lp["text_offset"][0] == 0
        for i, (tlp, top) in enumerate(zip(lp["token_logprobs"], lp["top_logprobs"])):
            assert tlp <= 0.0
            # dict keyed by token text: <= k when decoded strings collide
            assert 1 <= len(top) <= 3
            # greedy: the chosen token IS the argmax, so its logprob equals
            # the best alternative's (same scoring forward)
            assert abs(tlp - max(top.values())) < 1e-4, (i, tlp, top)
            assert lp["tokens"][i] in top

    def test_completions_logprobs_zero_keeps_chosen_only(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello world", "max_tokens": 3,
                                "temperature": 0, "logprobs": 0})
        assert r.status_code == 200, r.text
        lp = r.json()["choices"][0]["logprobs"]
        assert len(lp["token_logprobs"]) == 3
        assert lp["top_logprobs"] is None

    def test_chat_logprobs_shape(self, front):
        base, _ = front
        r = requests.post(base + "/v1/chat/completions",
                          json={"messages": [{"role": "user", "content": "hello world"}],
                                "max_tokens": 4, "temperature": 0,
                                "logprobs": True, "top_logprobs": 2})
        assert r.status_code == 200, r.text
        (choice,) = r.json()["choices"]
        content = choice["logprobs"]["content"]
        assert len(content) == 4
        for entry in content:
            assert set(entry) == {"token", "logprob", "bytes", "top_logprobs"}
            assert entry["logprob"] <= 0.0
            assert len(entry["top_logprobs"]) == 2
            assert bytes(entry["bytes"]).decode() == entry["token"]

    def test_validation_400s(self, front):
        base, _ = front
        bad = [
            {"prompt": "hello", "n": 0},
            {"prompt": "hello", "n": True},
            {"prompt": "hello", "n": 999},
            {"prompt": "hello", "logprobs": 6},
            {"prompt": "hello", "logprobs": True},  # bool is the CHAT form
            {"prompt": "hello", "n": 2, "stream": True},
            {"prompt": "hello", "logprobs": 2, "stream": True},
        ]
        for body in bad:
            r = requests.post(base + "/v1/completions",
                              json={"max_tokens": 2, **body})
            assert r.status_code == 400, (body, r.text)
        bad_chat = [
            {"logprobs": 3},  # int is the COMPLETIONS form
            {"top_logprobs": 2},  # requires logprobs: true
            {"logprobs": True, "top_logprobs": 21},
        ]
        for body in bad_chat:
            r = requests.post(base + "/v1/chat/completions",
                              json={"messages": [{"role": "user", "content": "hello"}],
                                    "max_tokens": 2, **body})
            assert r.status_code == 400, (body, r.text)

    def test_score_logprobs_matches_direct_forward(self, front):
        """The scoring program's values equal a hand-computed log-softmax
        over the same forward."""
        import jax.numpy as jnp_

        _, server = front
        ids, new_ids = [1, 2, 3], [5, 9]
        token_lps, top_ids, top_lps = server.score_logprobs(ids, new_ids, top_k=2)
        full = np.asarray([ids + new_ids], np.int32)
        from modelx_tpu.models.decode import pad_seq_len

        padded = np.zeros((1, pad_seq_len(full.shape[1])), np.int32)
        padded[0, : full.shape[1]] = full
        logits = server.family.forward(
            server.params, jnp_.asarray(padded), server.cfg, mesh=server.mesh
        )
        lp = np.asarray(jax.nn.log_softmax(np.asarray(logits, np.float32), axis=-1))
        for j, t in enumerate(new_ids):
            pos = len(ids) - 1 + j
            np.testing.assert_allclose(token_lps[j], lp[0, pos, t], rtol=1e-5)
            # top-2 from the same distribution
            order = np.argsort(lp[0, pos])[::-1][:2]
            np.testing.assert_array_equal(top_ids[j], order)


class TestNLogprobsEdges:
    """Review regressions: usage semantics under n, empty-content logprobs,
    and explicit-null defaults."""

    def test_prompt_tokens_counted_once_per_prompt(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello world tpu", "max_tokens": 2,
                                "temperature": 0, "n": 4, "ignore_eos": True})
        u = r.json()["usage"]
        assert u["prompt_tokens"] == 3  # not 12
        assert u["completion_tokens"] == 8
        assert u["total_tokens"] == 11

    def test_explicit_null_and_false_defaults_pass(self, front):
        base, _ = front
        r = requests.post(base + "/v1/completions",
                          json={"prompt": "hello", "max_tokens": 2,
                                "n": None, "logprobs": False})
        assert r.status_code == 200, r.text
        r = requests.post(base + "/v1/chat/completions",
                          json={"messages": [{"role": "user", "content": "hello"}],
                                "max_tokens": 2, "logprobs": True,
                                "top_logprobs": None})
        assert r.status_code == 200, r.text

    def test_logprobs_with_stop_at_offset_zero(self, front):
        """A stop sequence matching the first generated text keeps the
        logprobs shape valid (empty lists, not a 500)."""
        _, server = front
        tok = server.tokenizer()
        ids = tok.encode("hello world tpu")
        out = server.generate(np.asarray([ids], np.int32), max_new_tokens=3)
        first_word = tok.decode(out[0, 3:4].tolist())
        from modelx_tpu.dl.openai_api import run_completion
        from modelx_tpu.dl.serve import ServerSet

        sset = ServerSet({"m": server})
        body = run_completion(sset, {"prompt": "hello world tpu",
                                     "max_tokens": 3, "temperature": 0,
                                     "logprobs": 2, "stop": [first_word],
                                     "ignore_eos": True}, chat=False)
        (choice,) = body["choices"]
        assert choice["finish_reason"] == "stop"
        assert choice["text"] == ""
        lp = choice["logprobs"]
        assert lp["tokens"] == [] and lp["token_logprobs"] == []
        assert lp["top_logprobs"] == [] and lp["text_offset"] == []


class TestStreamResume:
    """Mid-stream failover resume (ISSUE 12) on the OpenAI surface: the
    same X-ModelX-Resume-* wire block as the native surface, validated and
    token-exact — the SSE text continuation emits only the text the
    client does not already have."""

    def _events(self, resp):
        assert resp.headers["Content-Type"] == "text/event-stream"
        raw = resp.content.decode()
        assert raw.endswith("data: [DONE]\n\n")
        return [json.loads(line[len("data: "):])
                for line in raw.split("\n\n")
                if line.startswith("data: ") and line != "data: [DONE]"]

    @pytest.fixture(scope="class")
    def cont_front(self, front):
        """The shared tiny model behind a CONTINUOUS-engine pod: resume
        needs per-step sample streams to rejoin."""
        from modelx_tpu.dl.serving_errors import resume_headers
        from modelx_tpu.registry.server import free_port as _free_port

        _, server = front
        sset = ServerSet({"m": server}, continuous_batch=True, max_slots=2,
                         stream_chunk_size=4)
        base = f"http://127.0.0.1:{_free_port()}"
        httpd = serve(sset, listen=base.rsplit("//", 1)[1])
        yield base, server, resume_headers
        httpd.shutdown()
        for cb in sset.cbatchers.values():
            cb.close()
            cb.release_device_state()

    # full-stream + per-k resumes over SSE (~2.5 s): slow set; the
    # validation test below keeps the OpenAI resume surface in tier-1
    @pytest.mark.slow
    def test_resume_continues_the_text_exactly(self, cont_front):
        base, server, resume_headers = cont_front
        tok = server.tokenizer()
        req = {"prompt": "hello world tpu", "max_tokens": 6,
               "temperature": 0, "stream": True}
        r = requests.post(base + "/v1/completions", json=req)
        assert r.status_code == 200, r.text
        full_text = "".join(c["text"] for e in self._events(r)
                            for c in e["choices"])
        # the emitted token ids come from the native surface (the caller
        # holding a resume block is the router, which has them)
        ids = tok.encode("hello world tpu")
        nat = requests.post(base + "/v1/generate",
                            json={"tokens": [ids], "max_new_tokens": 6,
                                  "stream": True},
                            stream=True)
        emitted = [json.loads(ln)["tokens"][0][0]
                   for ln in nat.raw.read().decode().strip().split("\n")[:-1]]
        assert tok.decode(emitted) == full_text.strip()
        for k in (2, 4):
            r2 = requests.post(base + "/v1/completions", json=req,
                               headers=resume_headers(emitted[:k], 0))
            assert r2.status_code == 200, r2.text
            cont = "".join(c["text"] for e in self._events(r2)
                           for c in e["choices"])
            # prefix text the client already has + the continuation =
            # the uninterrupted stream's text
            assert tok.decode(emitted[:k]) + cont == full_text

    def test_resume_validation_on_the_openai_surface(self, cont_front):
        base, server, resume_headers = cont_front
        req = {"prompt": "hello world tpu", "max_tokens": 4,
               "temperature": 0, "stream": True}
        # non-streaming resume is malformed (the block continues a STREAM)
        r = requests.post(base + "/v1/completions",
                          json={**req, "stream": False},
                          headers=resume_headers([1, 2], 0))
        assert r.status_code == 400, (r.status_code, r.text)
        assert r.json()["error"]["type"] == "invalid_request_error"
        # every owed token already emitted -> 422, OpenAI error shape
        r = requests.post(base + "/v1/completions", json=req,
                          headers=resume_headers([1, 2, 3, 4], 0))
        assert r.status_code == 422, (r.status_code, r.text)
        assert r.json()["error"]["type"] == "invalid_request_error"
        # seed header alone (both-or-neither)
        from modelx_tpu.dl.serving_errors import RESUME_SEED_HEADER
        r = requests.post(base + "/v1/completions", json=req,
                          headers={RESUME_SEED_HEADER: "7"})
        assert r.status_code == 400, (r.status_code, r.text)
