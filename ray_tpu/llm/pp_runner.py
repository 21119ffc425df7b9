"""Pipeline-parallel prefill/decode for the LLM engine.

Counterpart of vLLM's ``pipeline_parallel_size`` engine kwarg
(reference: llm/_internal/batch/stages/vllm_engine_stage.py:647) — the
reference delegates stage placement to vLLM over NCCL p2p; here the
pipeline is one SPMD program over a ``pipeline`` mesh axis, the same
design as the training pipeline (parallel/pipeline.py):

  - The stacked layer axis of the params AND the slot KV cache shard
    over the pipeline axis via ``shard_map`` — each stage holds only
    its ``L/pp`` layers and their cache rows. This is explicitly NOT
    plain GSPMD layer-axis sharding: XLA compiles a lax.scan over a
    sharded operand by all-gathering the full weight stack onto every
    device (measured), which defeats pipeline parallelism's purpose of
    fitting a model too big for one chip.
  - A step walks the stages with a static loop: ``lax.cond`` guards so
    only the owning stage runs its layer segment (real control flow —
    idle stages skip the compute), then a ``ppermute`` ring hop hands
    the activation to the next stage.
  - Embedding/sampling run replicated (cheap); the LM head runs on the
    last stage only and the logits ride one all_gather back.

The per-layer math is model_runner's own (make_prefill_body /
make_decode_body) — one implementation, two runners, so attention or
dtype fixes can never diverge between the pp=1 and pp>1 paths.

Single-token decode through a pipeline is latency-bound by design (one
stage computes at a time — vLLM's PP has the same property per batch);
PP here buys MEMORY capacity, with continuous batching providing the
overlap across requests at the engine level.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ray_tpu.llm import model_runner as mr
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.parallel.mesh import AXIS_PIPELINE
from ray_tpu.parallel.pipeline import pipeline_last_to_all


class PPRunner:
    """Drop-in for the subset of model_runner the engine uses on the
    non-speculative, unchunked path: ``init_slot_cache``, ``prefill``,
    ``decode`` (same signatures; params/cache live sharded)."""

    def __init__(self, config: TransformerConfig, pp: int,
                 devices=None):
        if config.n_layers % pp:
            raise ValueError(
                f"pipeline_parallel_size={pp} must divide n_layers "
                f"({config.n_layers})")
        devs = list(devices if devices is not None else jax.devices())
        if len(devs) < pp:
            raise ValueError(
                f"pipeline_parallel_size={pp} but only {len(devs)} "
                f"devices visible")
        self.c = config
        self.pp = pp
        self.mesh = Mesh(np.asarray(devs[:pp]), (AXIS_PIPELINE,))
        self._jit_prefill = jax.jit(self._sm_prefill, donate_argnums=(4,))
        self._jit_decode = jax.jit(self._sm_decode, donate_argnums=(3,))

    # -- placement ---------------------------------------------------------

    def _param_specs(self, params):
        """Layer stacks shard over the pipeline axis; everything else
        (embed/final_norm/lm_head) replicates."""
        return {
            k: jax.tree.map(
                lambda _, key=k: P(AXIS_PIPELINE) if key == "layers" else P(),
                v)
            for k, v in params.items()
        }

    def shard_params(self, params):
        specs = self._param_specs(params)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.tree.map(jax.device_put, params, shardings)

    def init_slot_cache(self, config, num_slots, max_len):
        cache = mr.init_slot_cache(config, num_slots, max_len)
        sh = NamedSharding(self.mesh, P(AXIS_PIPELINE))
        return {k: jax.device_put(v, sh) for k, v in cache.items()}

    # -- SPMD bodies -------------------------------------------------------

    def _stage_loop(self, x, kc, vc, seg):
        """Walk the pipeline: stage s runs ``seg`` on its local layers
        when the activation reaches it, then the ring hands x onward."""
        stage = jax.lax.axis_index(AXIS_PIPELINE)
        ring = [(i, (i + 1) % self.pp) for i in range(self.pp)]
        for s in range(self.pp):
            x, kc, vc = jax.lax.cond(
                stage == s,
                lambda ops: seg(*ops),
                lambda ops: ops,
                (x, kc, vc),
            )
            if s < self.pp - 1:
                x = jax.lax.ppermute(x, AXIS_PIPELINE, ring)
        return x, kc, vc

    def _last_stage_logits(self, x, params, dt):
        """LM head on the last stage only; replicated result."""
        stage = jax.lax.axis_index(AXIS_PIPELINE)
        logits = jax.lax.cond(
            stage == self.pp - 1,
            lambda v: mr._final_logits(v, params, self.c, dt),
            lambda v: jnp.zeros(v.shape[:2] + (self.c.vocab_size,),
                                jnp.float32),
            x,
        )
        return pipeline_last_to_all(logits)

    def _sm_prefill(self, params, tokens, true_len, slot, cache):
        c, dt = self.c, self.c.compute_dtype

        def inner(params, tokens, true_len, slot, kc, vc):
            _, S = tokens.shape
            positions = jnp.arange(S)
            x, rope = mr.embed_tokens(params, tokens, positions, c, dt)
            body = mr.make_prefill_body(c, dt, positions, rope, slot)

            def seg(x, kc, vc):
                x, (kc2, vc2) = jax.lax.scan(body, x,
                                             (params["layers"], kc, vc))
                return x, kc2, vc2

            x, kc, vc = self._stage_loop(x, kc, vc, seg)
            xl = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
            last = self._last_stage_logits(xl, params, dt)[0, 0]
            return last, kc, vc

        last, k_new, v_new = jax.shard_map(
            inner,
            mesh=self.mesh,
            in_specs=(self._param_specs(params), P(), P(), P(),
                      P(AXIS_PIPELINE), P(AXIS_PIPELINE)),
            out_specs=(P(), P(AXIS_PIPELINE), P(AXIS_PIPELINE)),
            check_vma=False,
        )(params, tokens, true_len, slot, cache["k"], cache["v"])
        return last, {"k": k_new, "v": v_new}

    def _sm_decode(self, params, tokens, positions, cache, temperature,
                   rng):
        c, dt = self.c, self.c.compute_dtype

        def inner(params, tokens, positions, kc, vc, temperature, rng):
            B = tokens.shape[0]
            T = kc.shape[2]
            x, rope = mr.embed_tokens(params, tokens[:, None],
                                      positions[:, None], c, dt)
            rope_tables = None
            if rope is not None:
                cos, sin = rope
                rope_tables = (cos[positions][:, None, None, :],
                               sin[positions][:, None, None, :])
            kmask = (jnp.arange(T)[None, :] <= positions[:, None])
            body = mr.make_decode_body(c, dt, positions, rope_tables,
                                       kmask, jnp.arange(B))

            def seg(x, kc, vc):
                x, (kc2, vc2) = jax.lax.scan(body, x,
                                             (params["layers"], kc, vc))
                return x, kc2, vc2

            x, kc, vc = self._stage_loop(x, kc, vc, seg)
            logits = self._last_stage_logits(x, params, dt)[:, 0]
            toks = mr.sample_tokens(logits, temperature, rng)
            return toks, logits, kc, vc

        toks, logits, k_new, v_new = jax.shard_map(
            inner,
            mesh=self.mesh,
            in_specs=(self._param_specs(params), P(), P(),
                      P(AXIS_PIPELINE), P(AXIS_PIPELINE), P(), P()),
            out_specs=(P(), P(), P(AXIS_PIPELINE), P(AXIS_PIPELINE)),
            check_vma=False,
        )(params, tokens, positions, cache["k"], cache["v"], temperature,
          rng)
        return toks, logits, {"k": k_new, "v": v_new}

    # -- engine-facing API (model_runner signatures) -----------------------

    def prefill(self, params, tokens, true_len, slot, cache, *, config):
        return self._jit_prefill(params, tokens, true_len, slot, cache)

    def decode(self, params, tokens, positions, cache, temperature, rng,
               *, config):
        return self._jit_decode(params, tokens, positions, cache,
                                temperature, rng)
