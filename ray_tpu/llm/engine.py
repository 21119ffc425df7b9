"""LLMEngine: continuous-batching JAX decode engine.

Counterpart of the reference's vLLM engine wrapper (reference:
llm/_internal/batch/stages/vllm_engine_stage.py — request queue, engine
step loop; serve side llm/_internal/serve/deployments/llm/). TPU-native
design: no paged attention, no CUDA graphs — a static slot cache
(model_runner.py) and a host-side scheduler:

  admit:  while a slot is free and requests wait, prefill one prompt
          (bucket-padded → few compiles) into the free slot;
  step:   one jitted decode advances every active slot by one token;
  retire: slots finishing (EOS / max_tokens / cache full) free up.

The whole engine is synchronous and single-threaded; concurrency comes
from serving it inside an actor (one engine per replica) and from the
batch dimension itself.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm import kv_pages, model_runner
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.kv_pages import KVPageError
from ray_tpu.llm.tokenizer import load_tokenizer
from ray_tpu.models import transformer as tfm

# Static top-k width of the device logprob output (one extra compile per
# distinct static value — so one cap for everyone, vLLM max_logprobs).
MAX_LOGPROBS = 20
# Static per-slot width of the logit_bias scatter in the device program.
MAX_LOGIT_BIAS = 16


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_tokens: list[int]
    params: SamplingParams
    generated: list[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: str | None = None
    # Adapter pool index, resolved ONCE at admission (an unload between
    # intake validation and admission fails the request, not the loop).
    lora_ix: int = 0
    # Per generated token (only when params.logprobs > 0):
    # {"token_id", "logprob", "top": {token_id: logprob, ...}}
    logprobs: "list[dict] | None" = None
    # Constraint driver (ray_tpu.llm.guided.GuidedJson) when the request
    # asked for response_format json mode; None otherwise.
    guided: "object | None" = None
    # Request-tracing context (trace_id, parent_span_id, sampled)
    # captured from the ambient contextvar at add_request — the engine
    # emits per-request "llm.prefill" / "llm.decode" spans into the
    # caller's trace (bounded: two spans per request, never per token).
    trace_ctx: Any = None
    t_add: float = 0.0       # enqueue wall time (queue-wait start)
    t_first: float = 0.0     # first-token wall time (decode start)
    # Disaggregated serving: the sealed KV-page record produced by a
    # prefill replica's prefill_detached(). When set, admission installs
    # the pages via _resume_into instead of running prefill.
    handoff: "dict | None" = None


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    token_ids: list[int]
    text: str
    finish_reason: str | None
    num_prompt_tokens: int
    # vLLM-style per-token logprobs (None unless requested).
    logprobs: "list[dict] | None" = None
    # Guided-decoding verdict: None, or an error string when the output
    # failed the constraint (truncated JSON / schema mismatch).
    error: "str | None" = None


class LLMEngine:
    def __init__(self, config: LLMConfig, params: Any = None):
        self.config = config
        self.model_config = config.resolve_model()
        self.tokenizer = load_tokenizer(config.tokenizer)
        c = self.model_config
        # layer_mixers (KDA's recurrent state) / kv_latent / layer_pattern /
        # sliding_window / experts_held: the slot engine's decode runs one
        # kind of layer and would run these wrongly in silence. First, so
        # that a model is refused by what it is and not only for its experts.
        tfm.refuse_decode(c)
        if c.n_experts > 0:
            raise NotImplementedError(
                "MoE decode is not wired into the slot engine yet; "
                "train with MoE (models.transformer + Train) and serve dense."
            )
        # len(tokenizer) counts added special tokens on HF tokenizers;
        # vocab_size alone excludes them and would let special-token ids
        # silently clamp in the embedding gather.
        try:
            tok_vocab = len(self.tokenizer)
        except TypeError:
            tok_vocab = getattr(self.tokenizer, "vocab_size", None)
        if tok_vocab is not None and tok_vocab > c.vocab_size:
            raise ValueError(
                f"tokenizer vocab ({tok_vocab}, incl. special tokens) exceeds "
                f"model vocab_size ({c.vocab_size}); special-token ids would "
                f"silently clamp in the embedding lookup. Use a model with "
                f"vocab_size >= {tok_vocab}."
            )
        # Engine cache capacity is capped by the model's position capacity.
        self.max_len = min(config.max_seq_len, c.max_seq_len)
        if params is None:
            if config.checkpoint_path:
                params = _load_checkpoint(config.checkpoint_path)
            else:
                params = tfm.init_params(jax.random.PRNGKey(config.seed), c)
        B = config.max_num_seqs
        # Pipeline parallelism (reference: vllm_engine_stage.py:647):
        # stage-sliced params + cache through shard_map (pp_runner.py).
        # The runner mirrors model_runner's prefill/decode signatures, so
        # the host-side scheduler below is identical either way.
        self._mr = model_runner
        pp = int(getattr(config, "pipeline_parallel_size", 1) or 1)
        if pp > 1:
            if int(config.tensor_parallel_size or 1) > 1:
                raise NotImplementedError(
                    "pipeline_parallel_size and tensor_parallel_size "
                    "cannot be combined yet")
            if config.prefill_chunk:
                raise NotImplementedError(
                    "chunked prefill is not supported with "
                    "pipeline_parallel_size > 1")
            if config.enable_prefix_caching:
                raise NotImplementedError(
                    "prefix caching is not supported with "
                    "pipeline_parallel_size > 1")
            if config.resolve_speculative_model() is not None:
                raise NotImplementedError(
                    "speculative decoding is not supported with "
                    "pipeline_parallel_size > 1")
            from ray_tpu.llm.pp_runner import PPRunner

            self._mr = PPRunner(c, pp)
        # Paged KV (reference: vLLM paged attention; llm/kv_pages.py):
        # fixed-size pages + per-slot block tables replace the dense
        # per-slot [max_len] cache. Host-side accounting lives in the
        # allocator; all scheduling below stays identical except where
        # pages are allocated/freed.
        self.page_size = int(getattr(config, "kv_page_size", 0) or 0)
        self.kv_alloc = None
        self._page_tables: list[list[int]] = []
        if self.page_size > 0:
            if (pp > 1 or int(config.tensor_parallel_size or 1) > 1
                    or config.resolve_speculative_model() is not None
                    or config.prefill_chunk):
                raise ValueError(
                    "kv_page_size (paged KV) is not supported together "
                    "with tensor/pipeline parallelism, speculative "
                    "decoding, or chunked prefill yet")
            self._max_blocks = -(-self.max_len // self.page_size)
            n_pages = int(getattr(config, "kv_num_pages", 0) or 0)
            if n_pages <= 0:
                n_pages = B * self._max_blocks + 1
            self.kv_alloc = kv_pages.KVPageAllocator(n_pages,
                                                     self.page_size)
            self._page_tables = [[] for _ in range(B)]
            self._block_tables = np.zeros((B, self._max_blocks), np.int32)
            cache = kv_pages.init_page_pool(c, n_pages, self.page_size)
        else:
            cache = self._mr.init_slot_cache(c, B, self.max_len)
        # Tensor parallelism (reference: vllm_engine_stage.py:646
        # tensor_parallel_size): TPU-natively this is pure PLACEMENT —
        # shard weights megatron-style (models.partition_specs) and the
        # slot KV cache on its kv_heads axis over a 1-D "tensor" mesh;
        # the SAME jitted prefill/decode then runs SPMD, with GSPMD
        # inserting the per-block psums. No second code path.
        self.mesh = None
        tp = int(config.tensor_parallel_size or 1)
        if pp > 1:
            params = self._mr.shard_params(params)
        elif tp > 1:
            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"tensor_parallel_size={tp} but only {len(devs)} "
                    f"devices visible")
            if c.n_heads % tp or c.kv_heads % tp:
                raise ValueError(
                    f"tensor_parallel_size={tp} must divide heads "
                    f"({c.n_heads}) and kv_heads ({c.kv_heads})")
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P

            from ray_tpu.parallel.sharding import shard_params

            self.mesh = Mesh(np.asarray(devs[:tp]), (tfm.AXIS_TENSOR,))
            params, _ = shard_params(params, self.mesh,
                                     tfm.partition_specs(c))
            kv_spec = NamedSharding(
                self.mesh, P(None, None, None, tfm.AXIS_TENSOR, None))
            cache = {k: jax.device_put(v, kv_spec) for k, v in cache.items()}
        self.params = params
        self.cache = cache
        self._device_info: "dict | None" = None
        # Host-side scheduling state (uploaded per decode call): keeping
        # positions on host avoids a device→host sync per slot per token.
        self.positions = np.zeros((B,), np.int32)
        self.last_tokens = np.zeros((B,), np.int32)
        self.temps = np.zeros((B,), np.float32)
        # Extended sampling (vLLM SamplingParams parity): per-slot knobs
        # uploaded to the advanced_sample program only when some active
        # slot needs it (plain batches keep the in-decode fast path).
        self.top_ks = np.zeros((B,), np.int32)
        self.top_ps = np.ones((B,), np.float32)
        self.min_ps = np.zeros((B,), np.float32)
        self.bias_ids = np.zeros((B, MAX_LOGIT_BIAS), np.int32)
        self.bias_vals = np.zeros((B, MAX_LOGIT_BIAS), np.float32)
        self.pres_pens = np.zeros((B,), np.float32)
        self.freq_pens = np.zeros((B,), np.float32)
        self.rep_pens = np.ones((B,), np.float32)
        self.seeds = np.zeros((B,), np.int32)
        # Device-resident penalty state (updated in-program).
        self._counts = jnp.zeros((B, c.vocab_size), jnp.int32)
        self._prompt_mask = jnp.zeros((B, c.vocab_size), jnp.bool_)
        self._plain = np.ones((B,), bool)  # slot uses the fast path
        # Slot is compatible with the speculative-decode path: sampling
        # reduces to raw-logits argmax (greedy_equivalent — top_k/top_p
        # never change the argmax, penalties do) and no logprobs are
        # requested (the spec path has no logprob plumbing).
        self._spec_ok = np.ones((B,), bool)
        self.slots: list[Request | None] = [None] * B
        self.waiting: collections.deque[Request] = collections.deque()
        # Prefix cache: token-tuple -> (k, v) device arrays [L, plen, KV,
        # Dh], LRU-ordered. Entries are written at prefix_block
        # granularity after a prompt's prefill and installed into a slot
        # on a later match (vLLM automatic-prefix-caching counterpart).
        self._prefix_pool: "collections.OrderedDict[tuple, tuple]" = (
            collections.OrderedDict())
        self.prefix_cache_hits = 0
        self.prefix_cache_queries = 0
        # Speculative decoding: a draft model shadows the batch (own
        # slot cache, prefilled alongside the target); each engine step
        # chains k-1 draft proposals and verifies the window with ONE
        # target pass (model_runner.verify), greedy acceptance host-side.
        self.draft = None
        # vLLM semantics: num_speculative_tokens = draft proposals per
        # verify window. The window itself is one longer (the last
        # emitted token leads it), so spec_k = proposals + 1 and a step
        # emits up to num_speculative_tokens + 1 tokens (drafts + bonus).
        self.spec_k = int(config.num_speculative_tokens) + 1
        dc = config.resolve_speculative_model()
        if dc is not None:
            if config.num_speculative_tokens < 1:
                raise ValueError(
                    f"num_speculative_tokens must be >= 1, got "
                    f"{config.num_speculative_tokens}")
            if dc.n_experts > 0:
                raise NotImplementedError("MoE draft models not supported")
            if dc.vocab_size != c.vocab_size:
                raise ValueError(
                    f"draft vocab_size ({dc.vocab_size}) must equal target "
                    f"vocab_size ({c.vocab_size}): proposals are target ids")
            if dc.max_seq_len < self.max_len:
                raise ValueError(
                    f"draft max_seq_len ({dc.max_seq_len}) < engine cache "
                    f"length ({self.max_len})")
            if config.speculative_checkpoint_path:
                dparams = _load_checkpoint(config.speculative_checkpoint_path)
            else:
                dparams = tfm.init_params(
                    jax.random.PRNGKey(config.speculative_seed), dc)
            self.draft = {
                "config": dc,
                "params": dparams,
                "cache": model_runner.init_slot_cache(dc, B, self.max_len),
            }
        self.spec_stats = {"proposed": 0, "accepted": 0, "spec_steps": 0,
                           "fallback_steps": 0}
        self._rng = jax.random.PRNGKey(config.seed + 1)
        self._step_count = 0
        # generate()/step() mutate slot state and the donated cache buffer;
        # serving replicas run threaded (max_concurrency > 1), so the engine
        # serializes itself rather than trusting every caller to.
        self._lock = threading.Lock()
        # Finished outputs for requests this caller did NOT submit (an
        # AsyncLLMEngine driving the same engine) are handed here instead
        # of being dropped — see AsyncLLMEngine, which registers itself.
        self._foreign_output_listener = None
        # Lazy per-tokenizer JSON token masker (guided decoding).
        self._json_masker = None
        # Multi-LoRA pool: per-slot adapter index 0 = null adapter.
        self.lora_mgr = None
        self.lora_ix = np.zeros((config.max_num_seqs,), np.int32)
        if config.lora:
            if (config.prefill_chunk or config.enable_prefix_caching
                    or config.resolve_speculative_model() is not None
                    or self._mr is not model_runner):
                raise ValueError(
                    "lora is not supported together with chunked "
                    "prefill, prefix caching, speculative decoding, or "
                    "pipeline parallelism")
            from ray_tpu.llm.lora import LoRAManager

            mc = self.model_config
            hdh = mc.n_heads * mc.head_dim
            kvdh = mc.kv_heads * mc.head_dim
            self.lora_mgr = LoRAManager(
                mc.n_layers,
                {"wq": (mc.d_model, hdh), "wk": (mc.d_model, kvdh),
                 "wv": (mc.d_model, kvdh), "wo": (hdh, mc.d_model)},
                max_adapters=int(config.lora.get("max_adapters", 8)),
                max_rank=int(config.lora.get("max_rank", 16)))

    # -- multi-LoRA (reference: LoraConfig serving surface) ----------------

    def add_lora(self, name: str, tensors, alpha: float = 16.0) -> None:
        """Load (or hot-overwrite) an adapter. ``tensors`` is a
        {"wq": (A, B), ...} dict, an .npz path, or a LoRAAdapter."""
        if self.lora_mgr is None:
            raise ValueError("engine was not configured with lora=")
        from ray_tpu.llm.lora import LoRAAdapter

        if isinstance(tensors, LoRAAdapter):
            ad = tensors
        elif isinstance(tensors, str):
            ad = LoRAAdapter.load(name, tensors, alpha=alpha)
        else:
            ad = LoRAAdapter(name, tensors, alpha=alpha)
        with self._lock:
            self.lora_mgr.add(ad)

    def remove_lora(self, name: str) -> bool:
        if self.lora_mgr is None:
            return False
        with self._lock:
            # Quiesce hook: indices still referenced by an in-flight
            # sequence are retired, not recycled — step() reclaims them
            # once the last referencing slot finishes (see LoRAManager).
            return self.lora_mgr.remove(name,
                                        active=self._active_lora_ixs())

    def list_loras(self) -> "list[str]":
        return [] if self.lora_mgr is None else self.lora_mgr.loaded()

    def _req_lora_ix(self, req: Request) -> int:
        name = (req.params.extra or {}).get("lora")
        if not name:
            return 0
        return self.lora_mgr.index_of(name)

    def _active_lora_ixs(self) -> set[int]:
        """Adapter indices referenced by slots still decoding."""
        return {int(self.lora_ix[i])
                for i, s in enumerate(self.slots) if s is not None}

    # -- request intake ----------------------------------------------------

    def add_request(self, request_id: str, prompt: str | list[int],
                    sampling_params: SamplingParams | None = None) -> None:
        sp = sampling_params or self.config.sampling_defaults
        if sp.logprobs > MAX_LOGPROBS:
            raise ValueError(
                f"logprobs={sp.logprobs} exceeds the engine cap "
                f"{MAX_LOGPROBS} (the device program's static top-k)")
        if len(sp.logit_bias) > MAX_LOGIT_BIAS:
            raise ValueError(
                f"logit_bias with {len(sp.logit_bias)} entries exceeds "
                f"the engine cap {MAX_LOGIT_BIAS} (the device program's "
                f"static scatter width)")
        for tid, _b in sp.logit_bias:
            if not 0 <= int(tid) < self.model_config.vocab_size:
                raise ValueError(
                    f"logit_bias token id {tid} outside vocab "
                    f"[0, {self.model_config.vocab_size})")
        toks = (self.tokenizer.encode(prompt) if isinstance(prompt, str)
                else list(prompt))
        toks = toks[: self.max_len - 1]
        if not toks:
            raise ValueError(
                f"request {request_id!r} has an empty prompt (prefill "
                f"needs at least one token to produce next-token logits)"
            )
        lname = (sp.extra or {}).get("lora")
        if lname:
            if self.lora_mgr is None:
                raise ValueError(
                    f"request selects LoRA adapter {lname!r} but the "
                    "engine has no lora= config")
            try:
                self.lora_mgr.index_of(lname)
            except KeyError as e:
                raise ValueError(str(e)) from None
        req = Request(request_id, toks, sp)
        if sp.response_format is not None:
            req.guided = self._make_guided(sp.response_format)
        from ray_tpu._private import worker_context

        req.trace_ctx = worker_context.get_trace_context()
        req.t_add = time.time()
        self.waiting.append(req)

    def has_unfinished(self) -> bool:
        return bool(self.waiting) or any(s is not None for s in self.slots)

    # -- disaggregated prefill/decode (zero-copy KV handoff) ---------------

    def prefill_detached(self, prompt: "str | list[int]",
                         sampling_params: "SamplingParams | None" = None,
                         ) -> dict:
        """Prefill-pool side of disaggregated serving: run ONE prompt's
        prefill, sample its first token, and return a self-contained
        KV-page record — then immediately free the slot and pages. The
        record's K/V arrays dominate its size, so returning it from a
        serve replica seals it metadata-only on the data plane (PR 8)
        and the decode replica pulls the payload p2p/arena — the head
        connection never carries the KV bytes."""
        if self.kv_alloc is None:
            raise ValueError(
                "prefill_detached requires paged KV (kv_page_size > 0)")
        sp = sampling_params or self.config.sampling_defaults
        if sp.response_format is not None:
            raise ValueError(
                "guided decoding cannot cross a prefill/decode handoff "
                "(the JSON automaton state is host-local)")
        if sp.logprobs > MAX_LOGPROBS:
            raise ValueError(
                f"logprobs={sp.logprobs} exceeds the engine cap "
                f"{MAX_LOGPROBS}")
        toks = (self.tokenizer.encode(prompt) if isinstance(prompt, str)
                else list(prompt))
        toks = toks[: self.max_len - 1]
        if not toks:
            raise ValueError("empty prompt")
        from ray_tpu._private import worker_context

        with self._lock:
            slot = next((i for i, s in enumerate(self.slots) if s is None),
                        None)
            if slot is None:
                from ray_tpu.exceptions import PendingCallsLimitError
                raise PendingCallsLimitError(
                    "no free prefill slot (all "
                    f"{len(self.slots)} busy)")
            import uuid as _uuid
            req = Request(f"pfd-{_uuid.uuid4().hex[:12]}", toks, sp)
            if self.lora_mgr is not None:
                req.lora_ix = self._req_lora_ix(req)
            req.trace_ctx = worker_context.get_trace_context()
            req.t_add = time.time()
            try:
                try:
                    last_logits = self._prefill_into(slot, toks,
                                                     lora_ix=req.lora_ix)
                except KVPageError as e:
                    # Retryable backpressure, same contract as a full
                    # admission queue.
                    from ray_tpu.exceptions import PendingCallsLimitError
                    raise PendingCallsLimitError(str(e)) from None
                self.slots[slot] = req
                if sp.seed is not None:
                    self.seeds[slot] = np.int32(
                        np.uint32(sp.seed & 0xFFFFFFFF))
                else:
                    self._rng, k = jax.random.split(self._rng)
                    self.seeds[slot] = np.int32(np.uint32(
                        int(jax.random.bits(k, dtype=jnp.uint32))))
                if sp.logprobs > 0:
                    req.logprobs = []
                tok = self._sample_host(np.asarray(last_logits), slot, req)
                req.t_first = time.time()
                self._emit_span(req, "llm.prefill", req.t_add, req.t_first,
                                {"prompt_tokens": len(toks),
                                 "handoff": True})
                pages = list(self._page_tables[slot])
                k_pages, v_pages = kv_pages.read_pages(
                    self.cache, jnp.asarray(np.asarray(pages, np.int32)))
                return {
                    "fmt": 1,
                    "model_id": self.config.model_id,
                    "page_size": self.page_size,
                    "prompt_tokens": list(toks),
                    "first_token": int(tok),
                    "seed": sp.seed,
                    "lora": (sp.extra or {}).get("lora") or "",
                    "logprobs0": (req.logprobs[0] if req.logprobs
                                  else None),
                    "sealed_at": time.time(),
                    "k": np.asarray(k_pages),
                    "v": np.asarray(v_pages),
                }
            finally:
                self._release_slot(slot)

    def add_handoff_request(self, request_id: str, handoff: dict,
                            sampling_params: "SamplingParams | None" = None,
                            ) -> None:
        """Decode-pool side: enqueue a request whose prompt K/V arrives
        as a prefill_detached() record. Admission installs the pages
        (_resume_into) instead of prefilling."""
        if self.kv_alloc is None:
            raise ValueError(
                "handoff decode requires paged KV (kv_page_size > 0)")
        for key in ("k", "v", "prompt_tokens", "first_token", "page_size"):
            if key not in handoff:
                raise ValueError(f"malformed handoff record: missing {key!r}")
        if int(handoff["page_size"]) != self.page_size:
            raise ValueError(
                f"handoff page_size {handoff['page_size']} != engine "
                f"page_size {self.page_size}")
        c = self.model_config
        k = np.asarray(handoff["k"])
        want = (c.n_layers, k.shape[1], self.page_size, c.kv_heads,
                c.head_dim)
        if k.ndim != 5 or k.shape != want:
            raise ValueError(
                f"handoff KV shape {k.shape} does not match engine "
                f"geometry {want}")
        if k.shape[1] > self._max_blocks:
            raise ValueError(
                f"handoff carries {k.shape[1]} pages > engine max "
                f"{self._max_blocks}")
        sp = sampling_params or self.config.sampling_defaults
        if handoff.get("lora") and not (sp.extra or {}).get("lora"):
            sp = dataclasses.replace(
                sp, extra={**(sp.extra or {}), "lora": handoff["lora"]})
        if (sp.extra or {}).get("lora") and self.lora_mgr is None:
            raise ValueError(
                f"handoff selects LoRA adapter "
                f"{(sp.extra or {}).get('lora')!r} but the engine has "
                "no lora= config")
        req = Request(request_id, list(handoff["prompt_tokens"]), sp)
        req.handoff = handoff
        from ray_tpu._private import worker_context

        req.trace_ctx = worker_context.get_trace_context()
        req.t_add = time.time()
        self.waiting.append(req)

    def _resume_into(self, slot: int, req: Request) -> int:
        """Install a handoff record's KV pages into ``slot`` and return
        the prefill-side first token. Raises KVPageError (caller
        requeues) when the pool can't cover the record."""
        h = req.handoff
        n = int(np.asarray(h["k"]).shape[1])
        pages = self._alloc_pages(n)
        self._page_tables[slot] = pages
        self._block_tables[slot, :] = 0
        self._block_tables[slot, :n] = pages
        self.cache = kv_pages.write_pages(
            self.cache, jnp.asarray(np.asarray(pages, np.int32)),
            jnp.asarray(h["k"]), jnp.asarray(h["v"]))
        return int(h["first_token"])

    # -- guided decoding (reference surface: response_format /
    #    json_mode_utils.py; enforcement is native here: ray_tpu.llm.guided)

    def _make_guided(self, rf) -> "object":
        from ray_tpu.llm import guided as gd

        if not isinstance(rf, dict) or rf.get("type") not in (
                "json_object", "json_schema", "text"):
            raise ValueError(
                f"response_format must be {{'type': 'json_object'|"
                f"'json_schema'|'text'}}, got {rf!r}")
        if rf.get("type") == "text":
            return None
        schema = None
        if rf.get("type") == "json_schema":
            js = rf.get("json_schema") or {}
            schema = js.get("schema") if isinstance(js, dict) else None
            if schema is not None and not isinstance(schema, dict):
                raise ValueError("json_schema.schema must be an object")
        if self._json_masker is None:
            tok = self.tokenizer
            v_tok = len(tok)
            texts = [tok.decode([i], skip_special_tokens=False)
                     if i != getattr(tok, "eos_token_id", -1) else ""
                     for i in range(v_tok)]
            # Pad to the model's (padded) vocab: ids past the tokenizer
            # range must never be sampled under a constraint.
            texts += [""] * (self.model_config.vocab_size - v_tok)
            self._json_masker = gd.JsonTokenMasker(
                texts, eos_id=int(getattr(tok, "eos_token_id", 0) or 0))
        return gd.GuidedJson(self._json_masker,
                             mode=rf["type"], schema=schema)

    def _guided_sample(self, req: Request, slot: int,
                       logits_row: np.ndarray) -> int:
        """Host-side constrained pick: mask the step's logits to the
        tokens the JSON automaton allows, then run the request's
        temperature pipeline over what remains."""
        sp = req.params
        mask = req.guided.allowed_mask()
        lg = logits_row.astype(np.float64)
        for tid, b in sp.logit_bias:
            lg[int(tid)] += float(b)
        if sp.repetition_penalty != 1.0:
            seen = np.unique(np.asarray(
                list(req.prompt_tokens) + list(req.generated), np.int64))
            vals = lg[seen]
            lg[seen] = np.where(vals > 0, vals / sp.repetition_penalty,
                                vals * sp.repetition_penalty)
        if (sp.presence_penalty or sp.frequency_penalty) and req.generated:
            cnt = np.bincount(np.asarray(req.generated, np.int64),
                              minlength=lg.shape[0])[: lg.shape[0]]
            lg -= (sp.frequency_penalty * cnt
                   + sp.presence_penalty * (cnt > 0))
        lg[~mask] = -np.inf
        if not np.isfinite(lg).any():
            # Automaton cornered (shouldn't happen: eos is allowed once
            # complete) — force eos so the request terminates.
            return int(self._json_masker.eos_id)
        if sp.temperature <= 0.0:
            tok = int(lg.argmax())
            dist = lg
        else:
            dist = self._host_filter(lg / max(sp.temperature, 1e-6), sp)
            dist[~mask] = -np.inf
            p = np.exp(dist - dist[np.isfinite(dist)].max())
            p[~np.isfinite(p)] = 0.0
            s = p.sum()
            if s <= 0:
                tok = int(lg.argmax())
            else:
                rng = np.random.default_rng(
                    int(np.uint32(self.seeds[slot]))
                    + len(req.generated) + 1)
                tok = int(rng.choice(len(p), p=p / s))
        if req.logprobs is not None:
            req.logprobs.append(self._host_logprob_entry(dist, sp, tok))
        req.guided.accept(tok)
        return tok

    # -- scheduling --------------------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if b >= n and b <= self.max_len:
                return b
        return self.max_len

    def _admit(self, outputs: list[RequestOutput]) -> None:
        # Batched admission (vLLM batches prefills): same-bucket prompts
        # prefill in ONE [N, S] program — fills the MXU batch dim and
        # amortizes dispatch. Prefix caching, chunked prefill, and
        # speculative drafts need per-prompt handling (different pos0 /
        # a draft mirror), so those engines admit sequentially.
        cfg = self.config
        batchable = (cfg.prefill_chunk == 0
                     and not cfg.enable_prefix_caching
                     and self.draft is None
                     # PP runs prefill through the PPRunner's shard_map
                     # (stage-sliced params); the plain-jit batched
                     # program would gather every stage's weights.
                     and self._mr is model_runner)
        admits: list[tuple[int, Request]] = []
        for slot in range(len(self.slots)):
            if self.slots[slot] is not None:
                continue
            while self.waiting:
                req = self.waiting.popleft()
                if self.lora_mgr is not None:
                    # Resolve the adapter index HERE: an unload racing
                    # the queue fails this one request with a clean
                    # output instead of throwing inside the step loop.
                    try:
                        req.lora_ix = self._req_lora_ix(req)
                    except KeyError as e:
                        req.finished = True
                        req.finish_reason = "error"
                        outputs.append(RequestOutput(
                            request_id=req.request_id, token_ids=[],
                            text="", finish_reason="error",
                            num_prompt_tokens=len(req.prompt_tokens),
                            error=str(e)))
                        continue
                admits.append((slot, req))
                break
        if not admits:
            return
        if (not batchable or len(admits) == 1
                or any(r.handoff is not None for _, r in admits)):
            for i, (slot, req) in enumerate(admits):
                try:
                    if req.handoff is not None:
                        tok0 = self._resume_into(slot, req)
                        self._finish_admit(slot, req, None, outputs,
                                           first_tok=tok0)
                    else:
                        last_logits = self._prefill_into(
                            slot, req.prompt_tokens, lora_ix=req.lora_ix)
                        self._finish_admit(slot, req,
                                           np.asarray(last_logits),
                                           outputs)
                except KVPageError:
                    # Page pool exhausted even after prefix eviction:
                    # requeue this and the rest at the queue head —
                    # finishing sequences will free pages.
                    self.waiting.extendleft(
                        r for _, r in reversed(admits[i:]))
                    return
            return
        if self.kv_alloc is not None:
            # Pre-allocate every admit's pages (the batched program needs
            # complete block tables); exhaustion requeues the remainder.
            kept: list[tuple[int, Request]] = []
            for i, (slot, req) in enumerate(admits):
                try:
                    pages = self._alloc_pages(
                        -(-len(req.prompt_tokens) // self.page_size))
                except KVPageError:
                    self.waiting.extendleft(
                        r for _, r in reversed(admits[i:]))
                    break
                self._page_tables[slot] = pages
                self._block_tables[slot, :] = 0
                self._block_tables[slot, :len(pages)] = pages
                kept.append((slot, req))
            admits = kept
            if not admits:
                return
        groups: dict[int, list] = {}
        for slot, req in admits:
            S = self._bucket(len(req.prompt_tokens))
            groups.setdefault(S, []).append((slot, req))
        B = len(self.slots)
        for S, group in sorted(groups.items()):
            if len(group) == 1:
                slot, req = group[0]
                last_logits = self._prefill_into(
                    slot, req.prompt_tokens, lora_ix=req.lora_ix)
                self._finish_admit(slot, req, np.asarray(last_logits),
                                   outputs)
                continue
            # Pad the group to the next power of two (bounded compile
            # count); pad rows use slot index B — out of range, dropped
            # by the scatter (model_runner.prefill_batch mode="drop").
            N = 1 << (len(group) - 1).bit_length()
            toks = np.zeros((N, S), np.int32)
            lens = np.ones((N,), np.int32)
            slots_arr = np.full((N,), B, np.int32)
            for j, (slot, req) in enumerate(group):
                L = len(req.prompt_tokens)
                toks[j, :L] = req.prompt_tokens
                lens[j] = L
                slots_arr[j] = slot
            lkw = {}
            if self.lora_mgr is not None:
                aix = np.zeros((N,), np.int32)
                for j, (_slot, r) in enumerate(group):
                    aix[j] = r.lora_ix
                lkw = {"lora": self.lora_mgr.lora_tree(),
                       "lora_ix": jnp.asarray(aix)}
            if self.kv_alloc is not None:
                # Pad group members carry out-of-range page ids in EVERY
                # block-table entry so the page scatter drops them.
                bts = np.full((N, self._max_blocks),
                              self.kv_alloc.num_pages, np.int32)
                for j, (slot, _req) in enumerate(group):
                    bts[j] = self._block_tables[slot]
                logits, self.cache = kv_pages.paged_prefill_batch(
                    self.params, jnp.asarray(toks), jnp.asarray(lens),
                    jnp.asarray(bts), self.cache,
                    config=self.model_config, **lkw)
            else:
                logits, self.cache = model_runner.prefill_batch(
                    self.params, jnp.asarray(toks), jnp.asarray(lens),
                    jnp.asarray(slots_arr), self.cache,
                    config=self.model_config, **lkw)
            logits_np = np.asarray(logits)
            for j, (slot, req) in enumerate(group):
                self._finish_admit(slot, req, logits_np[j], outputs)

    def _finish_admit(self, slot: int, req: Request,
                      last_logits: "np.ndarray | None",
                      outputs: list[RequestOutput],
                      first_tok: "int | None" = None) -> None:
        """Per-request state wiring after its prompt K/V is in ``slot``
        and its last-token logits are on host. ``first_tok`` short-cuts
        sampling for handoff resumes: the prefill replica already
        sampled token 0 (and emitted the llm.prefill span), so the
        decode side just installs it."""
        sp = req.params
        self.positions[slot] = len(req.prompt_tokens)
        self.slots[slot] = req
        self.temps[slot] = sp.temperature
        self.top_ks[slot] = max(0, sp.top_k)
        self.top_ps[slot] = sp.top_p
        self.min_ps[slot] = sp.min_p
        self.bias_ids[slot] = 0
        self.bias_vals[slot] = 0.0
        for j, (tid, b) in enumerate(sp.logit_bias[:MAX_LOGIT_BIAS]):
            self.bias_ids[slot, j] = int(tid)
            self.bias_vals[slot, j] = float(b)
        if self.lora_mgr is not None:
            self.lora_ix[slot] = req.lora_ix
        self.pres_pens[slot] = sp.presence_penalty
        self.freq_pens[slot] = sp.frequency_penalty
        self.rep_pens[slot] = sp.repetition_penalty
        self._plain[slot] = not sp.needs_advanced()
        # Guided slots pick host-side (masked); speculation's greedy
        # contract doesn't hold for them.
        self._spec_ok[slot] = (sp.greedy_equivalent() and sp.logprobs == 0
                               and req.guided is None)
        if sp.seed is not None:
            self.seeds[slot] = np.int32(np.uint32(sp.seed & 0xFFFFFFFF))
        else:
            self._rng, k = jax.random.split(self._rng)
            self.seeds[slot] = np.int32(
                np.uint32(int(jax.random.bits(k, dtype=jnp.uint32))))
        if sp.logprobs > 0:
            req.logprobs = []
        if first_tok is not None:
            tok = int(first_tok)
            if (req.logprobs is not None and req.handoff is not None
                    and req.handoff.get("logprobs0") is not None):
                req.logprobs.append(req.handoff["logprobs0"])
        elif req.guided is not None:
            tok = self._guided_sample(req, slot, last_logits)
        else:
            tok = self._sample_host(last_logits, slot, req)
        if not self._plain[slot]:
            # Seed the device-side penalty state: prompt token set +
            # the first sampled token.
            hist = np.zeros((self.model_config.vocab_size,), bool)
            hist[np.asarray(req.prompt_tokens, np.int64)] = True
            self._counts, self._prompt_mask = (
                model_runner.reset_slot_sampling(
                    self._counts, self._prompt_mask, jnp.int32(slot),
                    jnp.asarray(hist), jnp.int32(tok)))
        self.last_tokens[slot] = tok
        req.generated.append(tok)
        # Queue-wait + prefill up to the first sampled token, into the
        # request's trace (captured at add_request). Handoff resumes
        # skip it — the prefill replica emitted its own llm.prefill span
        # and the decode-side gap is the llm.handoff span.
        req.t_first = time.time()
        if first_tok is None:
            self._emit_span(req, "llm.prefill", req.t_add, req.t_first,
                            {"prompt_tokens": len(req.prompt_tokens)})
        self._maybe_finish(slot, outputs)

    def _prefill_into(self, slot: int, toks: list[int],
                      lora_ix: int = 0):
        """Write a prompt's K/V into ``slot`` (prefix-cache install +
        chunked or whole-prompt prefill) and return the last-token
        logits [V]."""
        if self.kv_alloc is not None:
            return self._prefill_into_paged(slot, toks, lora_ix=lora_ix)
        cfg = self.config
        L = len(toks)
        pos0 = 0
        if cfg.enable_prefix_caching:
            pos0 = self._install_cached_prefix(slot, toks)
        chunk = cfg.prefill_chunk if cfg.prefill_chunk > 0 else L - pos0
        last_logits = None
        off = pos0
        while off < L:
            take = min(chunk, L - off)
            # Padded width comes from the bucket set so chunk shapes
            # stay bounded (each distinct width is one XLA compile).
            S = self._bucket(take)
            if off + S > self.max_len:
                # Near the cache cap (rare): pad exactly to the cap —
                # an out-of-range dynamic_update_slice start would
                # silently clamp and shift the write onto earlier rows.
                S = self.max_len - off
                take = min(take, S)
            part = toks[off:off + take]
            padded = np.zeros((1, S), np.int32)
            padded[0, :len(part)] = part
            if off == 0 and len(part) == L:
                # Whole prompt in one go: within-chunk attention ([S,S]
                # scores, no history pass) is the cheapest path.
                lkw = {}
                if self.lora_mgr is not None:
                    lkw = {"lora": self.lora_mgr.lora_tree(),
                           "lora_ix": jnp.asarray([lora_ix], jnp.int32)}
                last_logits, self.cache = self._mr.prefill(
                    self.params, jnp.asarray(padded), jnp.int32(len(part)),
                    jnp.int32(slot), self.cache, config=self.model_config,
                    **lkw,
                )
            else:
                last_logits, self.cache = model_runner.prefill_at(
                    self.params, jnp.asarray(padded), jnp.int32(len(part)),
                    jnp.int32(off), jnp.int32(slot), self.cache,
                    config=self.model_config,
                )
            off += len(part)
        if cfg.enable_prefix_caching:
            self._store_prefix(slot, toks)
        if self.draft is not None:
            self._draft_prefill(slot, toks)
        return last_logits

    # -- paged KV (llm/kv_pages.py) ---------------------------------------

    def _alloc_pages(self, n: int) -> list[int]:
        """Allocate ``n`` pages, LRU-evicting prefix-cache entries under
        pressure (their pages are only reclaimed once no slot shares
        them — refcounts — so eviction never corrupts a live sequence)."""
        while True:
            try:
                return self.kv_alloc.alloc(n)
            except KVPageError:
                if not self._evict_one_prefix():
                    raise

    def _evict_one_prefix(self) -> bool:
        if self.kv_alloc is None or not self._prefix_pool:
            return False
        _, pages = self._prefix_pool.popitem(last=False)
        self.kv_alloc.free(pages)
        return True

    def _release_slot(self, slot: int) -> None:
        """Retire a slot: decref its KV pages (paged mode) and clear it.
        Every path that vacates a slot — normal finish, deadline
        eviction, _fail_all — must come through here or pages leak."""
        if self.kv_alloc is not None and self._page_tables[slot]:
            self.kv_alloc.free(self._page_tables[slot])
            self._page_tables[slot] = []
            self._block_tables[slot, :] = 0
        self.slots[slot] = None

    def _prefill_into_paged(self, slot: int, toks: list[int],
                            lora_ix: int = 0):
        """Paged-mode prompt prefill: pin any shared prefix pages, then
        allocate + fill the tail. Exception-safe: on pool exhaustion all
        refs taken here are released before the KVPageError propagates
        (the caller requeues the request)."""
        cfg = self.config
        L = len(toks)
        page = self.page_size
        pos0 = 0
        table: list[int] = list(self._page_tables[slot])
        if not table:
            if cfg.enable_prefix_caching:
                pos0, table = self._install_cached_prefix_paged(toks)
            n_tail = -(-L // page) - len(table)
            try:
                tail = self._alloc_pages(n_tail) if n_tail > 0 else []
            except KVPageError:
                self.kv_alloc.free(table)  # undo the prefix pins
                raise
            table = table + tail
            self._page_tables[slot] = table
            self._block_tables[slot, :] = 0
            self._block_tables[slot, :len(table)] = table
        bt = jnp.asarray(self._block_tables[slot])
        lkw = {}
        if self.lora_mgr is not None:
            lkw = {"lora": self.lora_mgr.lora_tree(),
                   "lora_ix": jnp.asarray([lora_ix], jnp.int32)}
        T = self._max_blocks * page
        S = min(self._bucket(L - pos0), T - pos0)
        padded = np.zeros((1, S), np.int32)
        padded[0, :L - pos0] = toks[pos0:]
        if pos0 == 0:
            last_logits, self.cache = kv_pages.paged_prefill(
                self.params, jnp.asarray(padded), jnp.int32(L), bt,
                self.cache, config=self.model_config, **lkw)
        else:
            # Tail-only prefill past a pinned prefix: pos0 is
            # page-aligned (installs hand out whole pages), so the tail
            # lands in freshly allocated pages and the shared ones stay
            # read-only — copy-on-write by construction.
            last_logits, self.cache = kv_pages.paged_prefill_at(
                self.params, jnp.asarray(padded), jnp.int32(L - pos0),
                jnp.int32(pos0), bt, self.cache,
                config=self.model_config)
        if cfg.enable_prefix_caching:
            self._store_prefix_paged(slot, toks)
        return last_logits

    def _install_cached_prefix_paged(self, toks: list[int]):
        """Paged prefix hit = page *pinning*, not a row copy: find the
        longest page-aligned common prefix in the pool and incref its
        pages. Returns (covered_tokens, pinned_pages)."""
        self.prefix_cache_queries += 1
        page = self.page_size
        limit = len(toks) - 1
        best_key, best_d = None, 0
        for key in self._prefix_pool:
            d = min(self._common_prefix(key, toks), limit)
            d = (d // page) * page
            if d > best_d:
                best_key, best_d = key, d
        if best_key is None:
            return 0, []
        self._prefix_pool.move_to_end(best_key)
        pages = list(self._prefix_pool[best_key][: best_d // page])
        self.kv_alloc.incref(pages)
        self.prefix_cache_hits += 1
        return best_d, pages

    def _store_prefix_paged(self, slot: int, toks: list[int]) -> None:
        """Pin this prompt's leading pages as a prefix-cache entry (the
        paged counterpart of _store_prefix — no bytes copied, the entry
        just holds a reference)."""
        page = self.page_size
        plen = ((len(toks) - 1) // page) * page
        if plen < page:
            return
        key = tuple(toks[:plen])
        for existing in list(self._prefix_pool):
            if len(existing) >= plen:
                if existing[:plen] == key:
                    self._prefix_pool.move_to_end(existing)
                    return  # covered by a (longer) entry's page prefix
            elif key[:len(existing)] == existing:
                self.kv_alloc.free(self._prefix_pool.pop(existing))
        pages = list(self._page_tables[slot][: plen // page])
        self.kv_alloc.incref(pages)
        self._prefix_pool[key] = pages
        while len(self._prefix_pool) > self.config.prefix_cache_entries:
            _, old = self._prefix_pool.popitem(last=False)
            self.kv_alloc.free(old)

    def device_info(self) -> dict:
        """What this engine runs on, read from its own arrays (not
        from jax's defaults): a caller learns the platform from the
        process that holds them. ``chips`` is the chip lease of that
        process (every one-chip process numbers its device 0). Placement
        is fixed at construction, so this is worked out once."""
        if self._device_info is None:
            devs = {d for leaf in jax.tree.leaves((self.params, self.cache))
                    if isinstance(leaf, jax.Array)
                    for d in leaf.sharding.device_set}
            first = min(devs, key=lambda d: d.id)
            self._device_info = {
                "platform": first.platform,
                "device_kind": first.device_kind,
                "n_devices": len(devs),
                "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            }
        return self._device_info

    def kv_stats(self) -> dict:
        """Paged-KV + prefix-cache accounting for telemetry/gauges."""
        out = {
            "paged": self.kv_alloc is not None,
            "prefix_hits": self.prefix_cache_hits,
            "prefix_queries": self.prefix_cache_queries,
        }
        if self.kv_alloc is not None:
            out.update(self.kv_alloc.stats())
        return out

    def _draft_prefill(self, slot: int, toks: list[int]) -> None:
        """Mirror the prompt into the draft model's slot cache so its
        proposals start from real context. One bucketed whole-prompt
        prefill suffices: prompts are capped at max_len - 1 and _bucket
        never exceeds max_len, so no chunking/cap handling is needed."""
        d = self.draft
        L = len(toks)
        S = self._bucket(L)
        padded = np.zeros((1, S), np.int32)
        padded[0, :L] = toks
        _, d["cache"] = model_runner.prefill(
            d["params"], jnp.asarray(padded), jnp.int32(L),
            jnp.int32(slot), d["cache"], config=d["config"])

    # -- prefix cache ------------------------------------------------------

    @staticmethod
    def _common_prefix(a: tuple, b: list[int]) -> int:
        n = min(len(a), len(b))
        for i in range(n):
            if a[i] != b[i]:
                return i
        return n

    def _install_cached_prefix(self, slot: int, toks: list[int]) -> int:
        """Find the entry sharing the longest common prefix with the
        prompt (block-rounded — an entry's sub-prefix is just a row
        slice, so divergence mid-entry still hits) and copy those K/V
        rows into the slot. Returns the number of prompt tokens covered
        (<= len(toks) - 1: at least one token must prefill to yield the
        next-token logits)."""
        self.prefix_cache_queries += 1
        block = max(1, self.config.prefix_block)
        limit = len(toks) - 1
        best_key, best_d = None, 0
        for key in self._prefix_pool:
            d = min(self._common_prefix(key, toks), limit)
            d = (d // block) * block
            if d > best_d:
                best_key, best_d = key, d
        if best_key is None:
            return 0
        self._prefix_pool.move_to_end(best_key)
        kp, vp = self._prefix_pool[best_key]
        if best_d < kp.shape[1]:
            kp, vp = kp[:, :best_d], vp[:, :best_d]
        self.cache = model_runner.install_prefix(
            self.cache, jnp.int32(slot), kp, vp)
        self.prefix_cache_hits += 1
        return best_d

    def _store_prefix(self, slot: int, toks: list[int]) -> None:
        """Save this prompt's K/V rows (block-rounded, capped to L-1 so
        the entry serves an identical future prompt) unless an existing
        entry already covers them; LRU-evict beyond capacity."""
        block = max(1, self.config.prefix_block)
        plen = ((len(toks) - 1) // block) * block
        if plen < block:
            return
        key = tuple(toks[:plen])
        for existing in list(self._prefix_pool):
            if len(existing) >= plen:
                if existing[:plen] == key:
                    self._prefix_pool.move_to_end(existing)
                    return  # covered by a (longer) entry's slice
            elif key[:len(existing)] == existing:
                del self._prefix_pool[existing]  # we supersede it
        kp, vp = model_runner.read_prefix(self.cache, jnp.int32(slot),
                                          length=plen)
        self._prefix_pool[key] = (kp, vp)
        while len(self._prefix_pool) > self.config.prefix_cache_entries:
            self._prefix_pool.popitem(last=False)

    @staticmethod
    def _host_filter(x: np.ndarray, sp: SamplingParams) -> np.ndarray:
        """Numpy mirror of filter_top_k_top_p with the same clamps as
        the device program: top_k clamped into [1, V], top_p <= 0 keeps
        (at least) the crossing token, so no user value can crash."""
        V = len(x)
        if sp.top_k and sp.top_k > 0:
            k = min(max(int(sp.top_k), 1), V)
            kth = np.partition(x, V - k)[V - k]
            x = np.where(x >= kth, x, -np.inf)
        if sp.top_p < 1.0:
            order = np.argsort(-x)
            px = np.exp(x[order] - x[order[0]])
            px = px / px.sum()
            cum = np.cumsum(px)
            keep_sorted = (cum - px) < sp.top_p
            keep_sorted[0] = True  # the crossing token is always kept
            cutoff = x[order[np.nonzero(keep_sorted)[0][-1]]]
            x = np.where(x >= cutoff, x, -np.inf)
        if sp.min_p > 0.0:
            # Same rule as the device program: drop tokens whose
            # probability is below min_p * max_prob (argmax survives).
            mp = min(max(sp.min_p, 0.0), 1.0)
            x = np.where(x >= x.max() + np.log(max(mp, 1e-10)), x, -np.inf)
        return x

    def _sample_host(self, logits: np.ndarray, slot: int, req: Request) -> int:
        """First-token sampling (host side, numpy): same pipeline as the
        device program — penalties -> temperature -> top_k/top_p ->
        sample — seeded from (seed, step=0) for determinism. Later
        tokens come from the in-decode or advanced_sample programs."""
        sp = req.params
        logits = logits.astype(np.float64)
        for tid, b in sp.logit_bias:
            logits[int(tid)] += float(b)
        if sp.repetition_penalty != 1.0:
            seen = np.unique(np.asarray(req.prompt_tokens, np.int64))
            vals = logits[seen]
            logits[seen] = np.where(vals > 0,
                                    vals / sp.repetition_penalty,
                                    vals * sp.repetition_penalty)
        # presence/frequency apply to GENERATED tokens only — none yet.
        if sp.temperature <= 0.0:
            tok = int(logits.argmax())
            dist = logits
        else:
            dist = self._host_filter(logits / max(sp.temperature, 1e-6), sp)
            p = np.exp(dist - dist.max())
            p = p / p.sum()
            rng = np.random.default_rng(int(np.uint32(self.seeds[slot])))
            tok = int(rng.choice(len(p), p=p))
        if req.logprobs is not None:
            # Same distribution the device program reports: the final
            # processed one (penalized for greedy rows, penalized+
            # temperature+filtered for sampled rows).
            req.logprobs.append(self._host_logprob_entry(dist, sp, tok))
        return tok

    @staticmethod
    def _host_logprob_entry(dist: np.ndarray, sp: SamplingParams,
                            tok: int) -> dict:
        """Logprob record over the final processed distribution."""
        logp = dist - np.logaddexp.reduce(dist[np.isfinite(dist)])
        n = min(sp.logprobs, len(logp))
        top_idx = np.argpartition(-logp, n - 1)[:n] if n > 0 else []
        return {"token_id": tok, "logprob": float(logp[tok]),
                "top": {int(i): float(logp[i])
                        for i in sorted(top_idx, key=lambda i: -logp[i])}}

    def _stop_ids(self, sp: SamplingParams) -> set[int]:
        stop = set(sp.stop_token_ids)
        if sp.ignore_eos:
            # vLLM ignore_eos: generate through the tokenizer's eos;
            # EXPLICIT stop_token_ids still apply.
            return stop
        eos = getattr(self.tokenizer, "eos_token_id", None)
        if eos is not None:
            stop.add(int(eos))
        return stop

    def _maybe_finish(self, slot: int, outputs: list[RequestOutput]) -> None:
        req = self.slots[slot]
        pos = int(self.positions[slot])
        reason = None
        text = None
        # vLLM min_tokens: every stop condition is suppressed until the
        # request has generated at least this many tokens.
        stops_armed = len(req.generated) >= req.params.min_tokens
        if (stops_armed and req.generated
                and req.generated[-1] in self._stop_ids(req.params)):
            req.generated.pop()  # don't surface the stop token
            if req.logprobs:
                req.logprobs = req.logprobs[: len(req.generated)]
            reason = "stop"
        elif stops_armed and req.params.stop:
            # Stop STRINGS (vLLM `stop`): end at the first occurrence,
            # trimming the match (and anything after) from the text.
            # Cheap per-token check: decode only a TAIL window (stop
            # strings are short; earlier occurrences were checked on
            # earlier tokens), sized so a match spanning the boundary
            # can't be missed; on a hit, decode once in full to find the
            # exact cut position.
            max_chars = max(len(s) for s in req.params.stop)
            window = min(len(req.generated), 16 + 2 * max_chars)
            tail = self.tokenizer.decode(req.generated[-window:])
            if any(s in tail for s in req.params.stop):
                decoded = self.tokenizer.decode(req.generated)
                # min_tokens suppressed earlier matches; on arming, only
                # matches extending past the suppressed prefix count
                # (vLLM keeps a search offset for the same reason).
                start = 0
                if req.params.min_tokens > 0:
                    prefix = self.tokenizer.decode(
                        req.generated[:req.params.min_tokens])
                    start = max(0, len(prefix) - max_chars + 1)
                cut = min((i for i in
                           (decoded.find(s, start)
                            for s in req.params.stop)
                           if i >= 0), default=-1)
                if cut >= 0:
                    text = decoded[:cut]
                    # Keep token_ids/logprobs consistent with the trimmed
                    # text: retain the shortest token prefix whose decode
                    # covers the kept text (the last kept token may decode
                    # to a partial overlap with the stop string).
                    n = len(req.generated)
                    while n > 0 and len(
                            self.tokenizer.decode(req.generated[:n - 1])
                    ) >= cut:
                        n -= 1
                    req.generated = req.generated[:n]
                    if req.logprobs:
                        req.logprobs = req.logprobs[:n]
                    reason = "stop"
        if reason is None:
            if len(req.generated) >= req.params.max_tokens:
                reason = "length"
            elif pos >= self.max_len - 1:
                reason = "length"  # KV cache exhausted
        if reason is not None:
            req.finished = True
            req.finish_reason = reason
            guided_err = None
            if req.guided is not None:
                _ok, guided_err = req.guided.finished_ok()
            outputs.append(RequestOutput(
                request_id=req.request_id,
                token_ids=list(req.generated),
                text=(text if text is not None
                      else self.tokenizer.decode(req.generated)),
                finish_reason=reason,
                num_prompt_tokens=len(req.prompt_tokens),
                logprobs=req.logprobs,
                error=guided_err,
            ))
            self._emit_span(
                req, "llm.decode", req.t_first or req.t_add, time.time(),
                {"tokens": len(req.generated), "finish_reason": reason})
            self._release_slot(slot)

    @staticmethod
    def _emit_span(req: Request, name: str, start: float, end: float,
                   attributes: "dict | None" = None) -> None:
        """Buffer one engine span into the request's trace (flushed on
        the owner's amortized rpc_report — zero per-span frames). No-op
        for untraced/unsampled requests, so batch generate() stays
        span-free."""
        tc = req.trace_ctx
        if not (tc and int(tc[2] or 0)):
            return
        import os

        from ray_tpu._private import traceplane

        traceplane.buffer_span({
            "event": "span",
            "name": name,
            "kind": "llm",
            "trace_id": tc[0],
            "span_id": traceplane.new_span_id(),
            "parent_span_id": tc[1],
            "pid": os.getpid(),
            "start": start,
            "end": end,
            "failed": False,
            "attributes": {"request_id": req.request_id,
                           **(attributes or {})},
        })

    def _ensure_page_capacity(self, active: list[int],
                              outputs: list[RequestOutput]) -> list[int]:
        """Paged mode: this step's KV write for slot b lands at logical
        position pos[b] — if that crosses into an unallocated page, grow
        the slot's block table now (on-demand allocation is what lets
        the pool overcommit). A slot that cannot get a page even after
        prefix eviction finishes with "length" — bounded, never wedged."""
        page = self.page_size
        still: list[int] = []
        for slot in active:
            blk = int(self.positions[slot]) // page
            table = self._page_tables[slot]
            if blk < len(table):
                still.append(slot)
                continue
            try:
                new = self._alloc_pages(1)
            except KVPageError:
                self._finish_forced(slot, "length", outputs)
                continue
            table.append(new[0])
            self._block_tables[slot, len(table) - 1] = new[0]
            still.append(slot)
        return still

    def _finish_forced(self, slot: int, reason: str,
                       outputs: list[RequestOutput]) -> None:
        """Finish a slot outside the normal stop rules (page-pool
        exhaustion): surface what was generated with ``reason``."""
        req = self.slots[slot]
        req.finished = True
        req.finish_reason = reason
        guided_err = None
        if req.guided is not None:
            _ok, guided_err = req.guided.finished_ok()
        outputs.append(RequestOutput(
            request_id=req.request_id,
            token_ids=list(req.generated),
            text=self.tokenizer.decode(req.generated),
            finish_reason=reason,
            num_prompt_tokens=len(req.prompt_tokens),
            logprobs=req.logprobs,
            error=guided_err,
        ))
        self._emit_span(
            req, "llm.decode", req.t_first or req.t_add, time.time(),
            {"tokens": len(req.generated), "finish_reason": reason})
        self._release_slot(slot)

    # -- the engine iteration ---------------------------------------------

    def step(self) -> list[RequestOutput]:
        """One engine iteration: admit waiting requests, then advance all
        active slots one token. Returns outputs finished this step."""
        outputs: list[RequestOutput] = []
        self._admit(outputs)
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if self.kv_alloc is not None and active:
            active = self._ensure_page_capacity(active, outputs)
        if not active:
            return outputs
        if self.draft is not None and all(self._spec_ok[s] for s in active):
            return self._spec_step(active, outputs)
        if self.draft is not None:
            self.spec_stats["fallback_steps"] += 1
            # Keep the draft cache in lockstep through fallback steps:
            # write draft K/V rows for the tokens this step consumes
            # (output discarded). Skipping this leaves permanent holes
            # the next _spec_step's chain would attend, collapsing
            # acceptance for the rest of those slots' lifetimes. Only
            # greedy slots can ever re-enter _spec_step, though — a
            # sampled slot's temperature is fixed at admit time and a
            # future greedy occupant re-prefills the draft slot — so an
            # all-sampled batch skips the draft pass entirely instead of
            # paying a full extra forward per token for rows nobody will
            # read.
            if any(self._spec_ok[s] for s in active):
                self._rng, dkey = jax.random.split(self._rng)
                _, _, self.draft["cache"] = model_runner.decode(
                    self.draft["params"], jnp.asarray(self.last_tokens),
                    jnp.asarray(self.positions), self.draft["cache"],
                    jnp.asarray(self.temps), dkey,
                    config=self.draft["config"])
        self._rng, key = jax.random.split(self._rng)
        lkw = {}
        if self.lora_mgr is not None:
            lkw = {"lora": self.lora_mgr.lora_tree(),
                   "lora_ix": jnp.asarray(self.lora_ix)}
        if self.kv_alloc is not None:
            toks, logits, self.cache = kv_pages.paged_decode(
                self.params,
                jnp.asarray(self.last_tokens),
                jnp.asarray(self.positions),
                jnp.asarray(self._block_tables),
                self.cache,
                jnp.asarray(self.temps),
                key,
                config=self.model_config,
                **lkw,
            )
        else:
            toks, logits, self.cache = self._mr.decode(
                self.params,
                jnp.asarray(self.last_tokens),
                jnp.asarray(self.positions),
                self.cache,
                jnp.asarray(self.temps),
                key,
                config=self.model_config,
                **lkw,
            )
        lp_info = None
        if not all(self._plain[s] for s in active):
            # Extended sampling program over this step's logits: replaces
            # the in-decode choice for the whole batch (plain slots get
            # identical semantics — penalties off, filters open).
            want_lp = any(self.slots[s] is not None
                          and self.slots[s].params.logprobs > 0
                          for s in active)
            steps = np.asarray([len(self.slots[s].generated)
                                if self.slots[s] is not None else 0
                                for s in range(len(self.slots))], np.int32)
            toks, chosen_lp, top_vals, top_ids, self._counts = (
                model_runner.advanced_sample(
                    logits, jnp.asarray(self.temps),
                    jnp.asarray(self.top_ks), jnp.asarray(self.top_ps),
                    jnp.asarray(self.min_ps),
                    jnp.asarray(self.pres_pens), jnp.asarray(self.freq_pens),
                    jnp.asarray(self.rep_pens), self._counts,
                    self._prompt_mask, jnp.asarray(self.seeds),
                    jnp.asarray(steps),
                    jnp.asarray(self.bias_ids), jnp.asarray(self.bias_vals),
                    max_logprobs=MAX_LOGPROBS if want_lp else 0))
            if want_lp:
                lp_info = (np.asarray(chosen_lp), np.asarray(top_vals),
                           np.asarray(top_ids))
        toks = np.asarray(toks)
        # Guided slots re-pick host-side under the JSON vocab mask (the
        # device program chose unconstrained; logits are this step's).
        guided_overrides: dict[int, int] = {}
        if any(self.slots[s] is not None and self.slots[s].guided
               is not None for s in active):
            logits_np = np.asarray(logits)
            for slot in active:
                req = self.slots[slot]
                if req is not None and req.guided is not None:
                    guided_overrides[slot] = self._guided_sample(
                        req, slot, logits_np[slot])
        # Only active slots advance; inactive slots' writes land at their
        # stale position and are reclaimed by the next prefill's mask.
        self.positions[active] += 1
        self._step_count += 1
        for slot in active:
            req = self.slots[slot]
            tok = guided_overrides.get(slot, int(toks[slot]))
            self.last_tokens[slot] = tok
            req.generated.append(tok)
            if (req.logprobs is not None and lp_info is not None
                    and slot not in guided_overrides):
                chosen_lp, top_vals, top_ids = lp_info
                n = req.params.logprobs
                req.logprobs.append({
                    "token_id": tok, "logprob": float(chosen_lp[slot]),
                    "top": {int(i): float(v)
                            for i, v in zip(top_ids[slot][:n],
                                            top_vals[slot][:n])},
                })
            self._maybe_finish(slot, outputs)
        if self.lora_mgr is not None and self.lora_mgr.has_retired():
            # Quiesce-complete check: recycle adapter slots whose last
            # referencing sequence finished this step.
            self.lora_mgr.reclaim(self._active_lora_ixs())
        return outputs

    def _spec_step(self, active: list[int],
                   outputs: list[RequestOutput]) -> list[RequestOutput]:
        """One speculative iteration (all active slots greedy).

        Chain k-1 draft-model decodes to propose a window, verify the
        whole window with one target pass, then accept the longest
        prefix where each proposal equals the target's greedy choice —
        plus the target's own next token as a bonus. Emitted tokens are
        bit-identical to plain greedy decoding (acceptance only keeps
        proposals the target would have produced), so speculation is
        purely a latency/throughput trade: 1 target pass per up-to-k
        tokens instead of per token.
        """
        d = self.draft
        k = self.spec_k
        cur = self.last_tokens.copy()
        pos = self.positions.copy()
        window = [cur.copy()]
        zero_t = jnp.zeros((len(self.slots),), jnp.float32)
        for _ in range(k - 1):
            self._rng, key = jax.random.split(self._rng)
            toks_j, _, d["cache"] = model_runner.decode(
                d["params"], jnp.asarray(cur), jnp.asarray(pos),
                d["cache"], zero_t, key, config=d["config"])
            cur = np.asarray(toks_j).copy()
            pos = pos + 1
            window.append(cur.copy())
        # One extra draft decode consuming the LAST proposal (output
        # discarded): if the full window is accepted, that proposal's
        # draft K/V row must exist — otherwise the draft cache carries a
        # permanently stale row and every later proposal degrades.
        self._rng, key = jax.random.split(self._rng)
        _, _, d["cache"] = model_runner.decode(
            d["params"], jnp.asarray(cur), jnp.asarray(pos), d["cache"],
            zero_t, key, config=d["config"])
        tokens_window = np.stack(window, axis=1)  # [B, k]

        logits, self.cache = model_runner.verify(
            self.params, jnp.asarray(tokens_window),
            jnp.asarray(self.positions), self.cache,
            config=self.model_config)
        greedy = np.asarray(logits.argmax(-1)).astype(np.int64)  # [B, k]

        self._step_count += 1
        self.spec_stats["spec_steps"] += 1
        for slot in active:
            prop = tokens_window[slot]
            g = greedy[slot]
            n = 0
            while n < k - 1 and prop[n + 1] == g[n]:
                n += 1
            self.spec_stats["proposed"] += k - 1
            self.spec_stats["accepted"] += n
            # prop[1..n] are the accepted drafts (== g[0..n-1]); g[n] is
            # the target's next token after them (the bonus).
            emitted = [int(t) for t in prop[1:n + 1]] + [int(g[n])]
            req = self.slots[slot]
            for tok in emitted:
                self.positions[slot] += 1
                self.last_tokens[slot] = tok
                req.generated.append(tok)
                self._maybe_finish(slot, outputs)
                if self.slots[slot] is None:
                    break
        return outputs

    # -- convenience batch API --------------------------------------------

    def generate(self, prompts: Iterable[str | list[int]],
                 sampling_params: "SamplingParams | list[SamplingParams] | None" = None,
                 ) -> list[RequestOutput]:
        """Run a batch of prompts to completion. ``sampling_params`` may
        be one SamplingParams for the whole batch or a list (one per
        prompt — vLLM generate() parity). Thread-safe: concurrent
        callers (threaded serving replicas) are serialized on the engine
        lock, and request ids are unique per call so interleaved batches
        can never swap outputs."""
        import uuid

        with self._lock:
            tag = uuid.uuid4().hex[:8]
            # Tokenize/validate every prompt BEFORE enqueuing any: a
            # mid-batch validation error must not leave earlier requests
            # orphaned in the waiting queue (their outputs would be
            # silently dropped by the next caller's step loop).
            toks_list = [
                (self.tokenizer.encode(p) if isinstance(p, str) else list(p))
                for p in prompts
            ]
            for i, toks in enumerate(toks_list):
                if not toks:
                    raise ValueError(f"prompt {i} of this batch is empty")
            if isinstance(sampling_params, (list, tuple)):
                if len(sampling_params) != len(toks_list):
                    raise ValueError(
                        f"sampling_params list ({len(sampling_params)}) must "
                        f"match prompts ({len(toks_list)})")
                sp_list = list(sampling_params)
            else:
                sp_list = [sampling_params] * len(toks_list)
            order = [f"req-{tag}-{i}" for i in range(len(toks_list))]
            for rid, toks, sp in zip(order, toks_list, sp_list):
                self.add_request(rid, toks, sp)
            mine = set(order)
            done: dict[str, RequestOutput] = {}
            # Step until THIS call's requests finish. Other requests
            # (an AsyncLLMEngine's) may share the batch; their outputs
            # go to the registered listener, never dropped.
            while len(done) < len(mine) and self.has_unfinished():
                for out in self.step():
                    if out.request_id in mine:
                        done[out.request_id] = out
                    elif self._foreign_output_listener is not None:
                        self._foreign_output_listener(out)
            return [done[rid] for rid in order]


class AsyncLLMEngine:
    """Async request-level driver over LLMEngine (reference:
    llm/_internal/batch/stages/vllm_engine_stage.py engine loop; vLLM's
    AsyncLLMEngine pattern). One background thread drives engine.step();
    callers submit requests and await per-request futures — so requests
    from CONCURRENT callers join the same running batch (true continuous
    batching across HTTP requests), instead of serializing whole batches
    behind the engine lock the way sync generate() does.

    Optionally streams: ``generate(..., stream=True)`` returns an async
    iterator of incremental token ids as the slot advances.

    Serving integration: ``generate(..., deadline=...)`` carries the
    request's wall-clock deadline into the decode loop — each step
    EVICTS owned requests whose deadline expired (waiting or mid-decode)
    with a typed ``TaskTimeoutError``, freeing their slots for live
    requests instead of finishing tokens nobody will read. ``snapshot()``
    reports the token-level batch view for replica telemetry.
    """

    def __init__(self, engine: LLMEngine):
        import queue as _queue

        self.engine = engine
        # Share the engine's own lock so sync generate() and this driver
        # can never interleave engine state mutations.
        self._lock = engine._lock
        self._waiters: dict[str, Any] = {}          # rid -> concurrent Future
        self._streams: dict[str, _queue.SimpleQueue] = {}
        self._seen: dict[str, int] = {}             # rid -> tokens streamed
        self._deadlines: dict[str, float] = {}      # rid -> wall-clock s
        self._evicted_deadline = 0
        self._wake = threading.Event()
        # If someone calls the sync engine.generate() while we have
        # requests in flight, its stepping delivers our outputs here.
        engine._foreign_output_listener = self._deliver
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="llm-engine-loop")
        self._thread.start()

    def _loop(self) -> None:
        while True:
            self._wake.wait()
            while True:
                with self._lock:
                    # Drive only while ASYNC-owned requests are pending.
                    # Foreign (sync generate()) requests are stepped by
                    # their own caller; spinning on them here would busy-
                    # loop forever if step() raises persistently after
                    # _fail_all cleared everything we own.
                    if (not (self._waiters or self._streams)
                            or not self.engine.has_unfinished()):
                        self._wake.clear()
                        break
                    try:
                        self._evict_expired()
                        outs = self.engine.step()
                        self._push_stream_tokens()
                    except Exception as e:  # noqa: BLE001
                        # A dead driver thread would hang every pending
                        # AND future request; fail them all instead and
                        # keep the loop alive (sync generate() would have
                        # propagated the exception to its caller too).
                        self._fail_all(e)
                        continue
                for out in outs:
                    self._deliver(out)

    def _deliver(self, out: RequestOutput) -> None:
        """Resolve the waiter/stream for one finished request. Called by
        the driver loop and (for batch-sharing) by sync generate()."""
        q = self._streams.pop(out.request_id, None)
        if q is not None:
            # Tokens from the finishing step never hit
            # _push_stream_tokens (the slot is cleared inside step()):
            # emit the unseen tail before the terminal output so the
            # incremental stream is complete.
            n = self._seen.get(out.request_id, 0)
            for tok in out.token_ids[n:]:
                q.put(int(tok))
            q.put(out)  # terminal: the RequestOutput itself
        self._seen.pop(out.request_id, None)
        self._deadlines.pop(out.request_id, None)
        fut = self._waiters.pop(out.request_id, None)
        if fut is not None and not fut.done():
            fut.set_result(out)

    def _evict_expired(self) -> None:
        """lock held. Continuous-batching admission control, evict side:
        owned requests whose serving deadline passed are failed with a
        typed TaskTimeoutError and removed from the engine's queues —
        a decode slot finishing tokens for a caller that already got
        HTTP 408 is pure waste under saturation."""
        if not self._deadlines:
            return
        now = time.time()
        expired = [rid for rid, dl in self._deadlines.items() if now > dl]
        if not expired:
            return
        from ray_tpu.exceptions import TaskTimeoutError

        for rid in expired:
            self._deadlines.pop(rid, None)
            exc = TaskTimeoutError(
                "TaskTimeoutError: request exceeded its deadline during "
                "LLM decode (evicted from the running batch)",
                where="llm_decode")
            fut = self._waiters.pop(rid, None)
            if fut is not None and not fut.done():
                fut.set_exception(exc)
            q = self._streams.pop(rid, None)
            if q is not None:
                q.put(exc)
            self._seen.pop(rid, None)
            self._evicted_deadline += 1
        gone = set(expired)
        import collections as _collections
        self.engine.waiting = _collections.deque(
            r for r in self.engine.waiting if r.request_id not in gone)
        # Through _release_slot, not a bare None: deadline eviction must
        # free the slot's KV pages (paged mode) or they leak for good.
        for i, r in enumerate(self.engine.slots):
            if r is not None and r.request_id in gone:
                self.engine._release_slot(i)

    def snapshot(self) -> dict:
        """Token-level batch view for replica telemetry (Replica
        .get_metrics surfaces it as the ``engine`` block)."""
        with self._lock:
            return {
                "waiting": len(self.engine.waiting),
                "active": sum(1 for s in self.engine.slots if s is not None),
                "slots": len(self.engine.slots),
                "owned": len(self._waiters) + len(self._streams),
                "evicted_deadline": self._evicted_deadline,
                "kv": self.engine.kv_stats(),
                **self.engine.device_info(),
            }

    def _fail_all(self, exc: Exception) -> None:
        """lock held. Resolve every async-owned pending request with the
        failure and evict only those from the engine's queues. Requests
        admitted by a concurrent sync ``engine.generate()`` caller stay:
        wiping them would make that caller's ``has_unfinished()`` loop
        exit early and KeyError on its own (vanished) request ids."""
        owned = set(self._waiters) | set(self._streams)
        for fut in self._waiters.values():
            if not fut.done():
                fut.set_exception(exc)
        self._waiters.clear()
        for q in self._streams.values():
            q.put(exc)  # aiter re-raises it
        self._streams.clear()
        self._seen.clear()
        for rid in owned:
            self._deadlines.pop(rid, None)
        import collections as _collections
        self.engine.waiting = _collections.deque(
            r for r in self.engine.waiting if r.request_id not in owned)
        for i, r in enumerate(self.engine.slots):
            if r is not None and r.request_id in owned:
                self.engine._release_slot(i)

    def _push_stream_tokens(self) -> None:
        """lock held. Emit tokens generated since the last step to any
        registered stream queues."""
        if not self._streams:
            return
        for slot_req in self.engine.slots:
            if slot_req is None:
                continue
            q = self._streams.get(slot_req.request_id)
            if q is None:
                continue
            n = self._seen.get(slot_req.request_id, 0)
            for tok in slot_req.generated[n:]:
                q.put(int(tok))
            self._seen[slot_req.request_id] = len(slot_req.generated)

    async def generate(self, prompt: "str | list[int]",
                       sampling_params: SamplingParams | None = None,
                       stream: bool = False,
                       deadline: "float | None" = None):
        """Awaitable single-request generation; with stream=True returns
        an async iterator yielding token ids then the final
        RequestOutput. ``deadline`` (wall-clock seconds) makes the
        decode loop evict this request once expired."""
        import asyncio
        import concurrent.futures
        import queue as _queue
        import uuid as _uuid

        loop = asyncio.get_running_loop()
        rid = f"areq-{_uuid.uuid4().hex[:12]}"
        # Tokenize off-loop (it is the only slow pre-admission work).
        if isinstance(prompt, str):
            toks = await loop.run_in_executor(
                None, self.engine.tokenizer.encode, prompt)
        else:
            toks = list(prompt)
        if stream:
            q: _queue.SimpleQueue = _queue.SimpleQueue()
            with self._lock:
                self.engine.add_request(rid, toks, sampling_params)
                self._streams[rid] = q
                self._seen[rid] = 0
                if deadline is not None:
                    self._deadlines[rid] = deadline
            self._wake.set()

            async def aiter():
                while True:
                    item = await loop.run_in_executor(None, q.get)
                    if isinstance(item, Exception):
                        raise item
                    yield item
                    if isinstance(item, RequestOutput):
                        return

            return aiter()
        fut: concurrent.futures.Future = concurrent.futures.Future()
        with self._lock:
            self.engine.add_request(rid, toks, sampling_params)
            self._waiters[rid] = fut
            if deadline is not None:
                self._deadlines[rid] = deadline
        self._wake.set()
        return await asyncio.wrap_future(fut)

    async def generate_from_handoff(self, handoff: dict,
                                    sampling_params: SamplingParams | None = None,
                                    deadline: "float | None" = None):
        """Awaitable continuation of a prefill_detached() record:
        installs the handed-off KV pages at admission and decodes under
        the same continuous batcher / deadline eviction as generate()."""
        import asyncio
        import concurrent.futures
        import uuid as _uuid

        rid = f"hreq-{_uuid.uuid4().hex[:12]}"
        fut: concurrent.futures.Future = concurrent.futures.Future()
        with self._lock:
            self.engine.add_handoff_request(rid, handoff, sampling_params)
            self._waiters[rid] = fut
            if deadline is not None:
                self._deadlines[rid] = deadline
        self._wake.set()
        return await asyncio.wrap_future(fut)


def _load_checkpoint(path: str):
    """npz (flat dotted keys) or orbax checkpoint directory."""
    import os

    if os.path.isfile(path) and path.endswith(".npz"):
        flat = dict(np.load(path))
        tree: dict = {}
        for k, v in flat.items():
            parts = k.split(".")
            node = tree
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = jnp.asarray(v)
        return tree
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer().restore(path)
