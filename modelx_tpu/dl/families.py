"""Model-family registry for the serving path: checkpoint tensor names ->
(config, partition rules, forward / generate / decode adapters).

The reference stores models without understanding them; the TPU serving
sidecar has to *execute* them. A ``Family`` is what the serving stack reads of
a family (dl/serve.py, continuous.py, kv_layout.py, kv_store.py,
openai_api.py, program_store.py, ttft.py read nothing else), and every field
of one is built by ONE adapter (``_causal``) from the family's model module,
imported at a field's first call and never with this file.

**The module interface.** A servable causal family is a module under
``modelx_tpu/models/`` with

- ``forward(params, tokens, cfg, *, kv_cache, cache_offset, mesh, ...) ->
  (logits, cache)``: a prefill where ``kv_cache`` is None, else a block of
  prompt positions or a decode step over the cache at ``cache_offset`` (a
  scalar, or ``[B]`` for ragged rows);
- ``init_kv_cache(cfg, batch, max_len)``: that cache, every layer's alike;

and, where it applies,

- ``config_from_hf(raw, dtype)``: its ``config.json`` is the source of its
  config (the row gives no shape reader; a checkpoint without one is refused);
- ``init_layer_state(cfg, slots, max_len)``, ``cache_kinds(cfg)`` (leaf ->
  "full" / "window" / "index" / "state" / "latent" / "counter") and
  ``published(cfg)`` (the ``"counters"`` its decode step accumulates, by the
  module's own ``*_COUNTERS`` tuples, and the ``"gauges"`` beside them, as
  /metrics names them): its layers keep caches of more than one kind
  (dl/kv_layout.LayerKindKV);
- ``check_context(cfg, last_pos)``: its positions are a learned table, and a
  generate loop that would run past it is refused up front.

What a module cannot be asked before it is imported — whether its forward
takes ``paged_table``, or ``valid_len`` / ``live``, what its per-kind forward
is passed, which tensor's dtype is the activation dtype, whether ``mesh`` is
handed on — is data in its row of ``FAMILIES``; nothing branches on a
family's name. A new architecture costs ``models/<family>.py`` and its
reference, its rules and one detection line in dl/sharding.py, one row here,
and its tests.

``infer_<family>_config(params)`` recovers an architecture from tensor shapes
for the families whose shapes say it (llama, qwen2, phi3, gemma2, mixtral,
gpt2, bert; ``config_for`` reconciles it with a pulled config.json); laguna,
minicpm_sala, deepseek_v2, nemotron_h and mimo_v2 read ``config.json`` alone.
``detect(tensor_names)`` picks the family from tensor NAMES
(dl/sharding.infer_family) — a header's index is enough, no weight is read.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import logging
import os
from typing import Any, Callable

import jax
import numpy as np

from modelx_tpu.dl.sharding import (
    BERT_RULES,
    DEEPSEEK_V2_RULES,
    GEMMA2_RULES,
    GPT2_RULES,
    LAGUNA_RULES,
    MIMO_V2_RULES,
    MINICPM_SALA_RULES,
    PHI3_RULES,
    LLAMA_RULES,
    MIXTRAL_RULES,
    NEMOTRON_H_RULES,
    QWEN2_RULES,
    Rules,
    infer_family,
)


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    rules: Rules
    infer_config: Callable[[dict], Any]
    forward: Callable[..., jax.Array]  # (params, tokens, cfg, mesh) -> logits
    generate: Callable[..., jax.Array] | None = None  # causal LMs only
    # ragged-batch decode (params, prompt, row_lens, cfg, mesh, max_new_tokens)
    # -> generated [B, max_new]; cached-decode families only — the serving
    # batcher uses it to coalesce concurrent generate requests
    generate_ragged: Callable[..., jax.Array] | None = None
    # (cfg, mesh) -> (forward-with-cache, init_kv_cache) for streaming decode
    # (models/decode.ChunkedDecoder); cached-decode families only
    decode_fns: Callable[..., tuple] | None = None
    # (cfg, mesh) -> forward over PAGED kv pools (kv_cache = page pools +
    # a block table; ops/paged_attention.py reads them in place) — the
    # continuous engine's fast paged chunk path; None = the engine falls
    # back to its generic dense-gather chunk for this family
    paged_decode_fns: Callable[..., Callable] | None = None
    # (sidecar config.json dict, abstract params) -> cfg, for a family whose
    # architecture leaves no trace in tensor shapes (layer kinds, per-layer
    # head counts, rope parameters, top-k, the experts held of those
    # published): ``infer_config`` then only says so
    config_from_sidecar: Callable[[dict, dict], Any] | None = None
    # (cfg, mesh) -> {"fwd": forward over a cache PER LAYER KIND,
    # "init_state": (slots, max_len) -> that state, "kinds": leaf -> "full" /
    # "window" / "counter", "counters", "gauges"} — the continuous engine
    # then keeps full layers [slots, max_len], window layers as rings, a
    # sparse layer's index of compressed keys ("index"), a linear-attention
    # layer's state ("state", no position axis) and a latent-attention layer's
    # one compressed line a position ("latent") (dl/kv_layout.LayerKindKV);
    # None = every layer's cache is alike
    layer_kind_decode_fns: Callable[..., dict] | None = None


def _shape(params: dict, name: str) -> tuple[int, ...]:
    return tuple(params[name].shape)


def _act_dtype(params: dict, name: str):
    """Activation dtype for a checkpoint: the (float) dtype of its embedding
    weight. A config whose dtype disagrees with the params breaks the cached
    decode path — the KV cache allocates cfg.dtype while k/v arrive in the
    params' compute dtype, and dynamic_update_slice rejects the mismatch.
    Non-float storage (e.g. int8 weight-only quant) computes in bfloat16."""
    import jax.numpy as jnp

    dt = params[name].dtype
    # jnp.issubdtype understands the extended float types (bfloat16 etc.)
    return dt if jnp.issubdtype(dt, jnp.floating) else jnp.bfloat16


# -- llama --------------------------------------------------------------------


def infer_llama_config(params: dict):
    """Recover the architecture from checkpoint tensor shapes."""
    from modelx_tpu.models import llama

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.q_proj.weight" in params:
        layers += 1
    q = _shape(params, "model.layers.0.self_attn.q_proj.weight")[0]
    kv = _shape(params, "model.layers.0.self_attn.k_proj.weight")[0]
    inter = _shape(params, "model.layers.0.mlp.gate_proj.weight")[0]
    # head_dim heuristics: big models use 128 (llama/mistral/qwen2-7B+)
    # unless that would leave fewer than 2 kv heads. kv=128 is genuinely
    # ambiguous — MQA-128 (32 q heads x 1 kv head, e.g. q=4096) vs
    # qwen2-0.5B (14 x 64, 2 kv heads, q=896) — so 128 also wins when the
    # checkpoint is clearly big (q//128 >= 8, the pre-qwen2 rule), which
    # keeps MQA llama checkpoints correct while 0.5B-class models (q//128
    # == 7) fall to 64
    if q % 128 == 0 and kv % 128 == 0 and (kv // 128 >= 2 or q // 128 >= 8):
        head_dim = 128
    elif q % 64 == 0 and kv % 64 == 0 and kv // 64 >= 2:
        head_dim = 64
    else:
        head_dim = max(q // 32, 32)
    if hidden <= 512:  # toy checkpoints
        head_dim = 32
    return llama.LlamaConfig(
        vocab_size=vocab,
        hidden_size=hidden,
        intermediate_size=inter,
        num_layers=layers,
        num_heads=q // head_dim,
        num_kv_heads=kv // head_dim,
        head_dim=head_dim,
        tie_embeddings="lm_head.weight" not in params,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


# -- mixtral ------------------------------------------------------------------


def infer_mixtral_config(params: dict):
    from modelx_tpu.models import mixtral

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.q_proj.weight" in params:
        layers += 1
    q = _shape(params, "model.layers.0.self_attn.q_proj.weight")[0]
    kv = _shape(params, "model.layers.0.self_attn.k_proj.weight")[0]
    w1 = "model.layers.0.block_sparse_moe.experts.w1.weight"
    num_experts, inter, _ = _shape(params, w1)
    head_dim = 128 if q % 128 == 0 and q // 128 >= 8 else max(q // 32, 32)
    if hidden <= 512:
        head_dim = 32
    return mixtral.MixtralConfig(
        vocab_size=vocab,
        hidden_size=hidden,
        intermediate_size=inter,
        num_layers=layers,
        num_heads=q // head_dim,
        num_kv_heads=kv // head_dim,
        head_dim=head_dim,
        num_experts=num_experts,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


# -- gpt2 ---------------------------------------------------------------------


def infer_gpt2_config(params: dict):
    from modelx_tpu.models import gpt2

    vocab, hidden = _shape(params, "wte.weight")
    n_pos = _shape(params, "wpe.weight")[0]
    layers = 0
    while f"h.{layers}.attn.c_attn.weight" in params:
        layers += 1
    # head count: standard gpt2 uses hidden/64 heads
    num_heads = max(hidden // 64, 1)
    if hidden <= 128:  # toy checkpoints
        num_heads = 4
    return gpt2.GPT2Config(
        vocab_size=vocab, n_positions=n_pos, hidden_size=hidden,
        num_layers=layers, num_heads=num_heads,
        dtype=_act_dtype(params, "wte.weight"),
    )


def infer_qwen2_config(params: dict):
    """Qwen2 = llama's decoder with qkv input biases; same inference plus
    the bias flag and qwen2's constants (rms eps 1e-6, rope theta 1e6 —
    every released Qwen2/2.5 uses these; shapes can't reveal them)."""
    cfg = infer_llama_config(params)
    return dataclasses.replace(cfg, qkv_bias=True, rms_eps=1e-6,
                               rope_theta=1_000_000.0)


# -- phi3 ---------------------------------------------------------------------


def infer_phi3_config(params: dict):
    """Phi-3 fused shapes: qkv rows = q + 2*kv with q == hidden in every
    released dense variant (mini 32x96, medium 40x128). head_dim: medium's
    GQA (kv != hidden rows) means 128; mini's MHA means hidden/32 = 96.
    Returns a llama.LlamaConfig — the module reuses llama's decoder.
    rope_theta=10000 is the 4k variants' value; the 128k variants need
    longrope scaling shapes can't reveal — apply_sidecar_config checks the
    pulled config.json and refuses those instead of mis-serving them."""
    from modelx_tpu.models import llama

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.qkv_proj.weight" in params:
        layers += 1
    qkv_rows = _shape(params, "model.layers.0.self_attn.qkv_proj.weight")[0]
    inter = _shape(params, "model.layers.0.mlp.gate_up_proj.weight")[0] // 2
    kv_rows = (qkv_rows - hidden) // 2
    if hidden <= 512:  # toy checkpoints: 4 q heads by convention
        head_dim = max(hidden // 4, 8)
    elif kv_rows != hidden:  # GQA (phi-3-medium): 128 everywhere released
        head_dim = 128
    else:  # MHA (phi-3-mini): 32 heads of hidden/32
        head_dim = hidden // 32
    return llama.LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=hidden // head_dim,
        num_kv_heads=kv_rows // head_dim, head_dim=head_dim,
        rope_theta=10000.0, rms_eps=1e-5, tie_embeddings=False,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


# -- gemma2 -------------------------------------------------------------------


def infer_gemma2_config(params: dict):
    """Gemma2 shapes are llama-like; head_dim is 256 in every released
    checkpoint except 27b (hidden 4608, head_dim 128, query_pre_attn_scalar
    hidden/heads = 144 instead of head_dim). Softcaps and the 4096 sliding
    window are architecture constants shapes can't reveal."""
    from modelx_tpu.models import gemma2

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.q_proj.weight" in params:
        layers += 1
    q = _shape(params, "model.layers.0.self_attn.q_proj.weight")[0]
    kv = _shape(params, "model.layers.0.self_attn.k_proj.weight")[0]
    inter = _shape(params, "model.layers.0.mlp.gate_proj.weight")[0]
    if hidden <= 512:  # toy checkpoints
        head_dim = 32
        qpas = float(head_dim)
        window = 16
    elif hidden >= 4608:  # gemma2-27b
        head_dim = 128
        qpas = float(hidden // (q // head_dim))
        window = 4096
    else:  # 2b / 9b
        head_dim = 256
        qpas = 256.0
        window = 4096
    return gemma2.Gemma2Config(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=q // head_dim,
        num_kv_heads=kv // head_dim, head_dim=head_dim,
        query_pre_attn_scalar=qpas, sliding_window=window,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


# -- bert ---------------------------------------------------------------------


def infer_bert_config(params: dict):
    from modelx_tpu.models import bert

    vocab, hidden = _shape(params, "bert.embeddings.word_embeddings.weight")
    max_pos = _shape(params, "bert.embeddings.position_embeddings.weight")[0]
    type_vocab = _shape(params, "bert.embeddings.token_type_embeddings.weight")[0]
    layers = 0
    while f"bert.encoder.layer.{layers}.attention.self.query.weight" in params:
        layers += 1
    inter = _shape(params, "bert.encoder.layer.0.intermediate.dense.weight")[0]
    num_heads = max(hidden // 64, 1)
    if hidden <= 128:
        num_heads = 4
    return bert.BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=num_heads, intermediate_size=inter,
        max_position_embeddings=max_pos, type_vocab_size=type_vocab,
        dtype=_act_dtype(params, "bert.embeddings.word_embeddings.weight"),
    )


def _bert_forward(params, tokens, cfg, mesh=None):
    """Returns the sequence output [B,S,E] (encoder family: 'logits' are
    features, argmax over E is not meaningful but harmless for probes)."""
    from modelx_tpu.models import bert

    return bert.forward(params, tokens, cfg)[0]


# -- the adapter: a family is its model module -----------------------------------


def _causal(name: str, rules: Rules, infer_config: Callable[[dict], Any] | None = None, *,
            module: str = "", paged_table: bool = False, told_lengths: bool = False,
            kind_forward: dict | None = None, hands_mesh: bool = True,
            embedding: str = "model.embed_tokens.weight") -> Family:
    """Every field of a generative ``Family`` from its model module
    (``modelx_tpu/models/<module or name>.py``, the interface in this file's
    docstring), imported at a field's first call. What differs between
    families is said here, by the row:

    - ``infer_config``: the shape reader; None = shapes cannot say, the config
      is ``config_from_hf`` of the ``config.json`` beside the weights, with the
      (float) dtype of ``embedding`` as the activation dtype;
    - ``paged_table``: its forward reads page pools through a ``paged_table``;
    - ``told_lengths``: some layers keep a STATE in place of keys and values —
      its forward takes ``valid_len`` / ``live``, and a block of prompt
      positions must come with its rows' real lengths;
    - ``kind_forward``: it keeps a cache per layer kind (``init_layer_state``,
      ``cache_kinds``, ``published``), and its forward over that state takes
      these keywords beside the dense one's;
    - ``hands_mesh``: False = its forward is never handed the mesh."""

    def mod():
        return importlib.import_module("modelx_tpu.models." + (module or name))

    def handed(mesh) -> dict:
        return {"mesh": mesh} if hands_mesh else {}

    def forward(params, tokens, cfg, mesh=None):
        return mod().forward(params, tokens, cfg, **handed(mesh))[0]

    def cached(cfg, mesh, **extra):
        """The module's forward as a decode closure. A state keeps what a
        padded bucket's tail adds, so a caller that lands a block of prompt
        positions on a ``told_lengths`` family says how many of them are real
        (``valid_len``; None = all), as the continuous engine does."""
        m = mod()

        def fwd(p, t, kv_cache, cache_offset, mesh=mesh, **told):
            if told_lengths and t.shape[1] > 1 and "valid_len" not in told:
                raise ValueError(
                    f"{name}: a block of prompt positions needs its rows' real "
                    "lengths (a state keeps what a padded tail adds): this family "
                    "streams through --continuous-batch")
            return m.forward(p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
                             **handed(mesh), **extra, **told)

        return fwd

    def decode_fns(cfg, mesh=None):
        m = mod()
        return cached(cfg, mesh), (lambda b, max_len: m.init_kv_cache(cfg, b, max_len))

    def paged_decode_fns(cfg, mesh=None):
        fwd = cached(cfg, mesh)
        return lambda p, t, kv_cache, cache_offset, table, mesh=mesh: fwd(
            p, t, kv_cache, cache_offset, mesh, paged_table=table)

    def layer_kind_decode_fns(cfg, mesh=None):
        m = mod()
        return {
            "fwd": cached(cfg, mesh, **kind_forward),
            "init_state": lambda slots, max_len: m.init_layer_state(cfg, slots, max_len),
            "kinds": m.cache_kinds(cfg),
            **m.published(cfg),  # "counters" and "gauges": what /metrics names
        }

    def loop_fns(cfg, mesh, last_pos: Callable[[], int], row_lens=None):
        """``decode_fns`` for the generic generate loops (models/decode.py): a
        ``told_lengths`` family's prompt block is told ``row_lens`` (None: the
        whole block), over a cache of whole buckets; a module with a
        ``check_context`` is asked first whether it has ``last_pos()`` positions."""
        check = getattr(mod(), "check_context", None)
        if check is not None:
            check(cfg, last_pos())
        fwd, init = decode_fns(cfg, mesh)
        if not told_lengths:
            return fwd, init
        from modelx_tpu.models.decode import pad_seq_len

        def told(p, t, kv_cache, cache_offset, mesh):
            block = {"valid_len": row_lens} if t.shape[1] > 1 else {}
            return fwd(p, t, kv_cache, cache_offset, mesh, **block)

        return told, (lambda b, max_len: init(b, pad_seq_len(max_len)))

    def generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
        from modelx_tpu.models import decode

        fwd, init = loop_fns(cfg, mesh, lambda: tokens.shape[1] + max_new_tokens)
        return decode.greedy_generate(
            fwd, init, params, tokens, max_new_tokens=max_new_tokens, mesh=mesh)

    def generate_ragged(params, tokens, row_lens, cfg, mesh=None, max_new_tokens=16,
                        **sampling):
        from modelx_tpu.models import decode

        # prefill touches positions [0, S); each row then decodes to row_len +
        # max_new (the batcher's bucket rounding can make this conservative by
        # less than one bucket at the very context edge)
        fwd, init = loop_fns(cfg, mesh, lambda: max(
            tokens.shape[1], int(np.max(np.asarray(row_lens))) + max_new_tokens), row_lens)
        return decode.ragged_greedy_generate(
            fwd, init, params, tokens, row_lens, max_new_tokens=max_new_tokens, mesh=mesh,
            **sampling)

    def refuse(params: dict):
        raise ValueError(
            f"tensor shapes do not say a {name} checkpoint's architecture: its "
            "config.json must lie beside the weights")

    def config_from_sidecar(sidecar: dict, params: dict):
        return mod().config_from_hf(sidecar, dtype=_act_dtype(params, embedding))

    return Family(
        name, rules, infer_config or refuse, forward, generate, generate_ragged, decode_fns,
        paged_decode_fns=paged_decode_fns if paged_table else None,
        config_from_sidecar=None if infer_config else config_from_sidecar,
        layer_kind_decode_fns=None if kind_forward is None else layer_kind_decode_fns)


# one row a family: what the adapter cannot ask of a module it has not imported
FAMILIES: dict[str, Family] = {f.name: f for f in (
    _causal("llama", LLAMA_RULES, infer_llama_config, paged_table=True),
    # same decoder implementation as llama — the bias params flow through
    # the param dict, so every llama entry point serves qwen2 unchanged
    _causal("qwen2", QWEN2_RULES, infer_qwen2_config, module="llama", paged_table=True),
    _causal("phi3", PHI3_RULES, infer_phi3_config, paged_table=True),
    _causal("gemma2", GEMMA2_RULES, infer_gemma2_config, paged_table=True),
    _causal("mixtral", MIXTRAL_RULES, infer_mixtral_config, paged_table=True),
    # its window layers' caches are rings where the state is per layer kind
    _causal("laguna", LAGUNA_RULES, kind_forward={"ring": True}),
    _causal("minicpm_sala", MINICPM_SALA_RULES, told_lengths=True, kind_forward={}),
    _causal("deepseek_v2", DEEPSEEK_V2_RULES, kind_forward={}),
    _causal("nemotron_h", NEMOTRON_H_RULES, told_lengths=True, kind_forward={},
            embedding="backbone.embeddings.weight"),
    # rings as laguna's; every leaf a line of a position's KV heads side by side
    _causal("mimo_v2", MIMO_V2_RULES, kind_forward={"ring": True}),
    # models/gpt2.forward reads ``mesh`` (its cache write's kernel rule looks
    # at it) and has never been handed one
    _causal("gpt2", GPT2_RULES, infer_gpt2_config, paged_table=True, hands_mesh=False),
    Family("bert", BERT_RULES, infer_bert_config, _bert_forward),
)}


logger = logging.getLogger("modelx.serve")


def sidecar_config(model_dir: str) -> dict | None:
    """The checkpoint's pulled ``config.json`` (the HF sidecar), if any.
    Shape inference recovers the architecture but NOT the RoPE parameters —
    rope_theta and rope_scaling leave no trace in tensor shapes."""
    try:
        with open(os.path.join(model_dir, "config.json")) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    return raw if isinstance(raw, dict) else None


# rope_scaling schemes that reshape position encoding at EVERY position:
# serving them with plain RoPE is wrong from token 0, so they refuse.
# Other schemes (llama3 / linear / dynamic-ntk) match plain RoPE inside
# the original context window — those warn (degraded long-context) but
# keep previously-deployable checkpoints loadable.
_ROPE_SCALING_REFUSED = ("longrope", "su", "yarn")


def config_for(family: Family, params: dict, model_dir: str):
    """The config of the checkpoint under ``model_dir``: from tensor shapes,
    reconciled with the pulled ``config.json`` (rope_theta overrides apply;
    unimplemented rope_scaling refuses before the weights stream) — or,
    for a family whose shapes cannot say, from ``config.json`` alone."""
    sidecar = sidecar_config(model_dir)
    if family.config_from_sidecar is not None:
        if sidecar is None:
            family.infer_config(params)  # raises, saying why
        return family.config_from_sidecar(sidecar, params)
    cfg = family.infer_config(params)
    if sidecar is not None:
        cfg = apply_sidecar_config(cfg, sidecar, family.name)
    return cfg


def apply_sidecar_config(cfg, sidecar: dict, family_name: str):
    """Reconcile a shape-inferred config with the checkpoint's config.json.

    ``rope_scaling`` is not implemented by this runtime. Schemes that
    change the encoding at every position (longrope/su/yarn — e.g. the
    phi-3-*-128k family) would decode garbage from the first token, so
    those checkpoints are REFUSED instead of silently mis-served
    (infer_phi3_config assumes the unscaled rope_theta=10000 of every 4k
    dense phi-3); window-extension schemes (llama3, linear, dynamic) warn
    and serve, correct within the pre-scaling window. A differing
    ``rope_theta`` is safe to honor: the sidecar's value replaces the
    inferred default, with a warning so the override is visible in logs."""
    scaling = sidecar.get("rope_scaling")
    if scaling:
        stype = (
            scaling.get("type") or scaling.get("rope_type")
            if isinstance(scaling, dict) else scaling
        )
        if not isinstance(stype, str) or stype.lower() in _ROPE_SCALING_REFUSED:
            raise ValueError(
                f"{family_name} checkpoint's config.json declares "
                f"rope_scaling ({stype!r}); this runtime implements "
                "unscaled RoPE only — refusing to mis-serve a long-context "
                "checkpoint (e.g. phi-3-*-128k)"
            )
        logger.warning(
            "%s config.json declares rope_scaling %r: not implemented — "
            "serving is exact only within the pre-scaling context window",
            family_name, stype,
        )
    theta = sidecar.get("rope_theta")
    if theta is not None and hasattr(cfg, "rope_theta"):
        try:
            theta = float(theta)
        except (TypeError, ValueError):
            logger.warning(
                "%s config.json rope_theta=%r is not numeric; keeping the "
                "inferred %s", family_name, theta, cfg.rope_theta,
            )
            return cfg
        if theta != float(cfg.rope_theta):
            logger.warning(
                "%s config.json rope_theta=%s overrides the shape-inferred %s",
                family_name, theta, cfg.rope_theta,
            )
            cfg = dataclasses.replace(cfg, rope_theta=theta)
    return cfg


def detect(tensor_names) -> Family:
    """Family from tensor names; raises for unrecognized checkpoints."""
    name = infer_family(list(tensor_names))
    if not name or name not in FAMILIES:
        raise ValueError(
            f"cannot determine model family from tensors ({list(tensor_names)[:4]}...); "
            f"supported: {sorted(FAMILIES)}"
        )
    return FAMILIES[name]


def abstract_params(infos: dict, rules: Rules | None = None, mesh=None,
                    quantize: str | None = None) -> dict:
    """ShapeDtypeStructs for a checkpoint known only by its header/manifest
    tensor index — everything config inference and AOT compilation need,
    before a single weight byte arrives. ``infos`` values need ``shape`` and
    either ``np_dtype()`` (st.TensorInfo) or ``dtype``. With rules+mesh the
    structs carry the placement shardings, so the compiled program matches
    the arrays the loader will deliver. ``quantize="int8"`` mirrors the
    loader's weight-only quantization: eligible 2-D weights become QTensor
    pytrees of structs (int8 data + f32 per-channel scale), so quantized
    deploys AOT-compile while their (halved) bytes stream — int8 TTFT pays
    max(load, compile), not the sum."""
    from modelx_tpu.dl.sharding import sharding_for

    if quantize not in (None, "int8"):
        raise ValueError(f"unsupported quantize mode {quantize!r}")
    if quantize:
        import numpy as np

        from modelx_tpu.ops import quant as qt
        from jax.sharding import NamedSharding, PartitionSpec

    out = {}
    for name, info in infos.items():
        dt = info.np_dtype() if hasattr(info, "np_dtype") else info.dtype
        sharding = sharding_for(name, rules, mesh) if rules is not None and mesh is not None else None
        shape = tuple(info.shape)
        if (
            quantize == "int8"
            and getattr(info, "members", None) is None
            and len(shape) == 2
            and qt.DEFAULT_ELIGIBLE.search(name) is not None
        ):
            # must mirror loader._quantized exactly: a mismatch compiles a
            # program the delivered params can't call
            scale_sharding = None
            if sharding is not None:
                spec = sharding.spec
                scale_sharding = NamedSharding(
                    mesh, PartitionSpec(spec[0] if len(spec) else None)
                )
            out[name] = qt.QTensor(
                q=jax.ShapeDtypeStruct(shape, np.int8, sharding=sharding),
                scale=jax.ShapeDtypeStruct((shape[0],), np.float32, sharding=scale_sharding),
            )
        else:
            out[name] = jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return out


def forward_program_key(family: Family, cfg, mode: str, token_shape: tuple,
                        mesh, param_sds: dict) -> str:
    """The aot_cache key for one (family, cfg, mode, shape, mesh, params)
    program — the single source of key truth shared by precompile_forward,
    precompile_score and the program-store bundler (dl/program_store.py), so
    a published bundle's artifact names always match what a warm boot asks
    the cache for."""
    from modelx_tpu.dl import aot_cache

    return aot_cache.cache_key(
        family.name, cfg, mode, token_shape,
        tuple(mesh.shape.items()) if mesh is not None else None,
        aot_cache.describe_sds(param_sds),
    )


def precompile_forward(family: Family, cfg, param_sds: dict, token_shape: tuple,
                       mesh=None, mode: str = "forward", cache_dir: str = ""):
    """AOT-compile the prefill forward for one token shape from abstract
    params — the weights do not need to exist yet, so a deploy overlaps this
    with the loader's byte streaming and the first request (or first token)
    meets an already-compiled program. Returns the compiled executable;
    call it with (params, tokens) of exactly these shapes/shardings.
    ``mode``: "forward" (logits), "argmax_all" (per-position argmax — the
    serve forward route), "argmax_last" (first decoded token — TTFT).
    ``cache_dir`` reuses a serialized export across processes (dl/aot_cache)
    so a warm pod start skips tracing+lowering entirely."""
    import jax.numpy as jnp

    if mode == "argmax_all":
        def fn(p, t):
            return jnp.argmax(family.forward(p, t, cfg, mesh=mesh), axis=-1)
    elif mode == "argmax_last":
        def fn(p, t):
            return jnp.argmax(family.forward(p, t, cfg, mesh=mesh)[:, -1, :], axis=-1)
    else:
        def fn(p, t):
            return family.forward(p, t, cfg, mesh=mesh)

    tok = jax.ShapeDtypeStruct(token_shape, jnp.int32)
    if cache_dir:
        from modelx_tpu.dl import aot_cache

        key = forward_program_key(family, cfg, mode, token_shape, mesh, param_sds)
        return aot_cache.load_or_compile(fn, (param_sds, tok), cache_dir, key)
    return jax.jit(fn).lower(param_sds, tok).compile()


def precompile_score(family: Family, cfg, param_sds: dict, token_shape: tuple,
                     top_k: int = 0, mesh=None, cache_dir: str = ""):
    """AOT-compile the scoring program (per-token logprobs of the given
    continuations, optional top-k alternatives) for one padded token shape.
    Body must stay identical to what serve.score_logprobs_rows historically
    jitted inline — routing it through here lets the export ride the aot
    cache and the program-store bundle like the forward ladder does.
    Call the result with (params, tokens) of exactly ``token_shape``."""
    import jax.numpy as jnp

    k = int(top_k)

    def fn(params, toks):
        logits = family.forward(params, toks, cfg, mesh=mesh)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)  # [B, Lb, V]
        nxt = jnp.concatenate(
            [toks[:, 1:], jnp.zeros((toks.shape[0], 1), jnp.int32)],
            axis=1,
        )
        chosen = jnp.take_along_axis(
            lp, nxt[..., None], axis=-1
        )[..., 0]  # position j scores token j+1
        if k:
            top_lp, top_id = jax.lax.top_k(lp, k)
            return chosen, top_id, top_lp
        return chosen, None, None

    tok = jax.ShapeDtypeStruct(token_shape, jnp.int32)
    if cache_dir:
        from modelx_tpu.dl import aot_cache

        key = forward_program_key(
            family, cfg, f"score:{int(top_k)}", token_shape, mesh, param_sds
        )
        return aot_cache.load_or_compile(fn, (param_sds, tok), cache_dir, key)
    return jax.jit(fn).lower(param_sds, tok).compile()
