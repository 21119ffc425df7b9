"""Decoder-only transformer LM: the flagship model family.

The reference framework ships no model implementations of its own — its
Train/RLlib/llm libraries orchestrate torch models (TorchTrainer wraps a
user nn.Module, reference: train/torch/torch_trainer.py:11; ray.llm
delegates to vLLM engines, llm/_internal/batch/stages/vllm_engine_stage.py)
— so the north-star recipes (GPT-2 125M DDP, Llama-family FSDP/TP;
BASELINE.json) need a model library here. This one is TPU-first:

  - Params are plain pytrees with a **stacked layer axis** so the forward
    pass is one ``lax.scan`` over layers: compile time is O(1) in depth
    and XLA pipelines the per-layer DMAs.
  - Compute in bfloat16, params in float32, statistics/softmax in float32
    (the MXU-native mixed-precision recipe).
  - Attention uses the O(T)-memory blockwise/Pallas-flash ops
    (ray_tpu.ops.attention); sequence parallelism composes via
    ray_tpu.ops.ring_attention in the shard_map path.
  - ``partition_specs()`` exports the megatron-style TP layout (heads and
    ffn sharded over the ``tensor`` axis); FSDP layering on top is done by
    parallel.sharding.infer_param_specs, so dp/fsdp/tp/sp all come from
    the same param tree.

Two architectures behind one config:
  - ``arch="gpt2"``  — learned positions, LayerNorm, GELU MLP, tied head.
  - ``arch="llama"`` — RoPE, RMSNorm, SwiGLU, GQA, untied head.

The ``llama`` arch also takes, a field or a few each (``TransformerConfig``
says what each means, the presets which published model sets it):

  - **layers that are not all alike**: a period of (windowed, rope) kinds
    (``layer_pattern``, anchored to the published numbering by
    ``first_layer``), or **a mixer a layer** (``layer_mixers``: a name of
    ``mixers.MIXERS`` for every layer, or ``"ffn"``, NO mixer, which makes
    the model a stack of **single-sublayer blocks**: ``x <- x + f(norm(x))``
    with ONE ``f`` a layer). Such a model keeps, beside the leaves every
    layer has (the norms, ``attn/wo``, the router, the FFN), ONE STACK A
    KIND OF MIXER for the leaves only that kind has, and a layer holds its
    own sublayer's leaves alone (``_holds``). With a period of P > 1 the
    scan runs over WHOLE PERIODS and unrolls a period's P layers in its
    body, so each position's kind is static (a windowed layer compiles to
    the kernel that skips tiles, never to a ``cond``); layers left over
    after the last whole period run unrolled behind the scan.
  - **what a token mixer is** (softmax attention plain, latent or
    differential, KDA, Gated DeltaNet, a Mamba-2 state-space layer, a
    Mamba-1 selective scan, a gated memory unit, cross-attention) is its
    record's, ``models/mixers.py``. **To add a mixer**: a record there, its
    line in ``mixers.MIXERS`` and its fields here; nothing in ``_block``,
    ``forward``, ``lm_loss``, ``init_params`` or ``partition_specs``, which
    take a mixer's leaves, specs, scope, function and counters from it.
  - **a memory the stack carries beside x**: a layer whose record
    ``writes`` hands out named arrays (a Mamba-1 layer its scan output, an
    attention layer its keys and values) and every later layer whose
    record ``reads`` that name finds them in its ``Ctx`` (``_writes``,
    ``run_stack``); a model with no reader carries x alone.
  - **experts**: capacity slots over a mesh or dropless on one chip
    (``ops/moe.py``), a router that reads the block's FIRST norm, held
    experts (``experts_held``: one expert-parallel rank's share), a shared
    expert with or without a gate, a sigmoid router whose bias the train
    step moves by rule (``make_train_step``), experts with no gate
    projection; **leading dense layers** are a SECOND stack of parameters
    (``dense_layers``) run ahead of the scan over ``layers``.
  - norms on the sublayers' outputs (``post_norm``), zero-centred norms, a
    scaled embedding.

Every part runs under a ``jax.named_scope`` from ``SCOPES``, so each
device instruction of a profiler trace says which part it belongs to
(its ``op_name``; ``chipbench/scopes.py`` reads it). Scopes are metadata:
the compiled program is the same with and without them.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import math
import sys
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import PartitionSpec as P

from ray_tpu.models import mixers
from ray_tpu.models.mixers import (  # noqa: F401  (read from here by name)
    _BATCH,
    ATTN_PART_SCOPES,
    ATTN_SCOPES,
    GATE_SCOPE,
    MLA_SCOPE,
    Counter,
    _expand_gqa,
    _norm_weight,
)
from ray_tpu.ops import linear_attention, moe, state_space
from ray_tpu.ops.attention import (FLASH_LSE_NAME, FLASH_OUT_NAME, attention,
                                   dot_product_attention)
from ray_tpu.ops.layers import (
    apply_rope,
    gelu_mlp,
    layer_norm,
    rms_norm,
    rope_frequencies,
    swiglu,
)
from ray_tpu.parallel.mesh import (
    AXIS_EXPERT,
    AXIS_SEQUENCE,
    AXIS_TENSOR,
)
from ray_tpu.parallel.sharding import constrain

# The parts of a train step, as the named scopes spell them: in forward()
# ``embed``, then ``layers`` (the layer scan or loop; alone on an
# instruction it is the scan's own traffic) around per block ``attn_norm``,
# ``attn`` (projections, rope, scores, output projection), ``mlp_norm``,
# ``mlp`` (``moe`` with experts; the dropless dispatch and the shared
# expert open their own sub-scopes inside it, ``ops.moe.SCOPES``; a router
# that reads the first norm runs under ``moe`` / ``moe_router`` ahead of
# ``attn``; an expert model's leading dense layers run under ``mlp``), then
# ``final_norm`` and
# ``head_loss`` (head matmul + every cross entropy); in make_train_step
# ``grad_accum`` (the micro-batch scan's sums) and ``optimizer`` (update +
# apply). What a mixer opens inside ``attn`` is in ``models/mixers.py``
# (``ATTN_SCOPES``, ``ATTN_PART_SCOPES``, ``MLA_SCOPE``, ``GATE_SCOPE``).
SCOPES = ("embed", "layers", "attn_norm", "attn", "mlp_norm", "mlp", "moe",
          "final_norm", "head_loss", "grad_accum", "optimizer")
# The token mixers ``layer_mixers`` may name (``mixers.MIXERS`` has a
# record each), the LINEAR ones among them (a state carried along the
# sequence; "gmu" and "cross" carry none: they read an earlier layer's
# memory), and ``FFN_ONLY``, which names
# no mixer: the layer is its FFN alone, and makes the model a stack of
# single-sublayer blocks (``single_sublayer``).
MIXERS = tuple(mixers.MIXERS)
LINEAR_MIXERS = ("kda", "gdn", "ssm", "ssm1")
FFN_ONLY = "ffn"
# Inside ``attn`` and inside ``mlp`` / ``moe``: the norm of the sublayer's
# output and the residual add behind it.
POST_NORM_SCOPE = "post_norm"

# Which tree's scopes an executable carries. jax's compile-cache key leaves
# metadata out, so a step loaded from the cache would keep the scope names
# of whatever tree compiled it. ``SCOPES_ID`` names the bytes of every file
# that opens a scope of the step and rides on one instruction of the train
# step (the step counter's add) as a frontend attribute, which the key does
# take: a tree in which one of them differs compiles its own step
# (``tests/test_model_scopes.py`` holds jax to it on a real cache).
# (``ray_tpu.ops.attention`` the attribute is the function, not the module.)
SCOPE_FILES = (__file__, moe.__file__,
               sys.modules[attention.__module__].__file__,
               linear_attention.__file__, state_space.__file__,
               mixers.__file__)


def _scopes_id(files=SCOPE_FILES) -> str:
    digest = hashlib.sha1()
    for path in files:
        with open(path, "rb") as source:
            digest.update(source.read())
    return "scopes." + digest.hexdigest()[:8]


SCOPES_ID = _scopes_id()


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304          # GPT-2 BPE padded to a multiple of 128
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    n_kv_heads: int | None = None    # < n_heads → GQA (llama arch only)
    d_ff: int | None = None          # default: 4*d_model (gpt2), 8/3*d (llama)
    max_seq_len: int = 1024
    arch: str = "gpt2"               # "gpt2" | "llama"
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"          # activation/compute dtype
    param_dtype: str = "float32"
    tie_embeddings: bool | None = None  # default: True for gpt2, False for llama
    attn_impl: str = "auto"          # ray_tpu.ops.attention dispatch
    remat: bool = True               # checkpoint each layer (HBM↔FLOPs trade)
    # Checkpoint policy: "full" recomputes the whole layer (max memory
    # savings); "dots" saves matmul outputs and recomputes only cheap
    # elementwise ops — ~MXU-free backward at a fraction of full remat's
    # 1/3 FLOP overhead. Small models should prefer "dots". Under either,
    # a layer whose attention ran the Pallas kernel also keeps that
    # kernel's output and logsumexp (``layer_of``).
    remat_policy: str = "full"       # "full" | "dots"
    scan_layers: bool = True         # lax.scan over layers vs unrolled loop
    # Rows a block of the LM head + loss: ``lm_loss`` computes logits/CE
    # in blocks of this many tokens inside a scan, so the [B,T,vocab]
    # float32 logits tensor is never materialized (peak-memory, not
    # FLOPs, is what caps batch size on a single chip). 0 = ONE block of
    # all the rows: the same op with its gradients made in its forward
    # (``ce_impl`` "fused"), on the operands as they are (under a mesh
    # every device works on its own rows). Blocks are for memory, not for
    # speed; set this where a step's float32 logits do not fit.
    loss_chunk: int = 0
    # Token-accuracy metric in the CE loss: an argmax sweep over the
    # [*, vocab] float32 logits per block (and in ``ce_impl``
    # "checkpoint"'s remat recompute). Throughput-bench configs turn it
    # off (the metric dict then reports accuracy 0.0).
    ce_accuracy: bool = True
    # Backward strategy of a ``loss_chunk`` a caller sets (at 0 it is
    # "fused"). "fused": custom-VJP that makes dlogits = softmax - onehot
    # INSIDE the forward scan and saves only dx / dhead, so each chunk's
    # logits are computed once a step. "checkpoint": jax.checkpoint around
    # the chunk body, whose backward makes every chunk's logits again (96.0k
    # against 90.9k tok/s/chip at GPT-2 124M, ``benchmarks/ab_results.jsonl``).
    ce_impl: str = "fused"           # "fused" | "checkpoint"
    # Mixture of Experts (llama arch only; 0 = dense FFN). Greenfield vs
    # the reference (SURVEY.md §2.4: EP absent upstream) — see ops/moe.py.
    n_experts: int = 0
    expert_top_k: int = 2
    # Slots an expert and token group, over the even share; what overflows
    # is dropped. None is DROPLESS: every assignment is computed (sorted
    # dispatch + grouped matmul, one chip's experts only).
    expert_capacity_factor: float | None = 1.25
    # Renormalise the top-k gates to sum to 1 (Mixtral) or use the
    # softmax's values as they are (OLMoE, ``norm_topk_prob: false``).
    expert_norm_topk: bool = True
    router_aux_weight: float = 0.01  # x the load-balance term
    router_z_weight: float = 0.0     # x mean logsumexp(router logits)^2
    # RMSNorm with a learned weight on q and k ahead of RoPE. True: over
    # the WHOLE projection, all heads together (OLMoE). "head": over each
    # head's ``head_dim`` values, ONE weight for all the query heads of a
    # layer and one for its key heads (Trinity).
    qk_norm: bool | str = False
    # -- what describes a model whose layers are not all alike (llama arch)
    d_head: int | None = None        # a head's width; default d_model/n_heads
    norm_eps: float = 1e-5           # RMSNorm / LayerNorm epsilon
    # One period of layer kinds, repeated n_layers / len times: per
    # position (windowed, rope). Empty: every layer is the arch's own
    # (full causal attention; RoPE in the llama arch).
    layer_pattern: tuple[tuple[bool, bool], ...] = ()
    # The PUBLISHED number of the model's first layer, which anchors the
    # pattern: layer i is position ``(first_layer + i) % len``. A number:
    # any n_layers, and leading dense layers take their kinds from the
    # pattern too. None runs as 0 and says less (where the stack stands in
    # the published numbering is not known), so a part-period and a dense
    # stack ahead of the pattern are refused.
    first_layer: int | None = None
    sliding_window: int | None = None  # keys a query of a windowed layer sees
    expert_activation: str = "silu"  # "silu" (SwiGLU) | "relu" (ReGLU)
    # What the router reads: "mlp_norm" (the experts' own input) or
    # "attn_norm" (the block's first norm: the logits are made ahead of
    # attention, the experts still run on the second norm's output).
    router_input: str = "mlp_norm"
    # (rank, of): the parameters hold rank ``rank``'s n_experts / of
    # consecutive experts of every layer, one expert-parallel rank's
    # share; the router and the top-k stay over all n_experts and the
    # block adds the held experts' part of the sum (dropless only).
    experts_held: tuple[int, int] | None = None
    # -- a DeepSeek-V3-shaped model (llama arch) --------------------------
    # Latent attention (MLA): the width of the compressed key / value
    # latent (None: plain attention). A head's query and key are then
    # ``d_head_nope`` wide without positions plus ``d_head_rope`` with
    # RoPE, the rotary key ONE vector all heads share; its value is
    # ``d_head_v`` wide. ``d_head`` is not read.
    kv_latent: int | None = None
    d_head_nope: int = 0
    d_head_rope: int = 0
    d_head_v: int = 0
    # The first ``n_dense_layers`` of the ``n_layers`` have a dense FFN
    # ``d_ff_dense`` wide in place of experts: a second stack of
    # parameters, ``params["dense_layers"]``, ahead of ``params["layers"]``.
    n_dense_layers: int = 0
    d_ff_dense: int | None = None
    # A gated FFN this wide that every token passes through beside its
    # routed experts, ungated (0: none).
    d_ff_shared: int = 0
    # How the router scores an expert: "softmax" over all of them, or a
    # "sigmoid" each (the gates are then the chosen scores, renormalised
    # under ``expert_norm_topk``).
    router_score: str = "softmax"
    # A bias an expert, a leaf of the parameters (``layers/router/b``),
    # added to the scores for the CHOICE alone: no gate and no gradient
    # sees it. The train step sets it, after the optimizer (whose update
    # and decay never touch it), to ``b + router_bias_rate x sign(mean
    # load - the expert's load)`` from the step's own assignment counts.
    router_bias: bool = False
    router_bias_rate: float = 0.0
    expert_gate_scale: float = 1.0   # x the gates, after renormalising
    # -- a model whose layers mix tokens in more than one way (llama arch) --
    # The token mixer of every one of the ``n_layers``, by name: a key of
    # ``mixers.MIXERS`` ("attn", "kda", "gdn", "ssm") or "ffn" (NO mixer:
    # the layer is its FFN alone). Empty: attention everywhere. The scan's
    # period is read off the list (``_period``).
    layer_mixers: tuple[str, ...] = ()
    kda_heads: int = 0               # (value) heads of a linear layer
    kda_head_dim: int = 0            # a linear head's key AND value width,
    #                                  and the rank of KDA's two low-rank maps
    kda_conv: int = 4                # positions the short convolution reads
    # Query / key heads of a Gated DeltaNet layer where they are fewer than
    # its ``kda_heads`` value heads: value head j reads key head ``j //
    # (kda_heads / linear_key_heads)``. None: as many.
    linear_key_heads: int | None = None
    # Latent attention rotates its ``d_head_rope``-wide parts (RoPE); False:
    # no positional encoding at all, the part is a plain shared key.
    latent_rope: bool = True
    # -- an afmoe-shaped model (llama arch, plain attention) ---------------
    # Attention's output, a query head and column, times ``sigmoid(W_g
    # h)`` of the block's normed input, ahead of ``wo`` (``attn/wg``).
    attn_gate: bool = False
    # RMSNorm with a learned weight on each sublayer's OUTPUT, ahead of
    # the residual add (``ln1_post``, ``ln2_post``): four norms a block.
    post_norm: bool = False
    # x the embedding's rows as they enter the stream (muP: sqrt(d_model));
    # the head is not scaled.
    embed_scale: float = 1.0
    # -- a qwen3_next-shaped model (llama arch) ----------------------------
    # The share of a head's width that RoPE rotates: its FIRST ``head_dim x
    # rope_fraction`` values (the two halves of those paired), the rest as
    # they are (plain attention only).
    rope_fraction: float = 1.0
    # Every RMSNorm with a weight the width of the stream or of an
    # attention head (the block norms, the final norm, ``qk_norm="head"``)
    # scales by ``1 + w``, ``w`` made 0 (a linear mixer's output norm stays
    # the plain form, its weight made 1).
    norm_zero_centred: bool = False
    # The shared expert's output times ``sigmoid(w_s . h)``, ONE number a
    # token (``mlp/shared_gate`` [D]).
    shared_expert_gate: bool = False
    # -- a nemotron_h-shaped model (llama arch) ----------------------------
    # A state-space ("ssm") layer: ``kda_heads`` heads of ``kda_head_dim``
    # channels and a convolution over ``kda_conv`` positions, as the other
    # linear mixers'; a state ``ssm_state`` wide a channel, ``B`` and ``C``
    # shared by the ``kda_heads / ssm_groups`` heads of a group (also the
    # groups of the output's gated norm), in chunks of ``ssm_chunk``.
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    # The convolution adds a bias a channel. Every state-space model here
    # has it; the field stays only because the benchmark's control
    # ``--break ssm_conv_bias=false`` runs the program without one.
    ssm_conv_bias: bool = True
    # Plain attention beside ``layer_mixers`` rotates its heads (RoPE);
    # False: no positional encoding at all.
    attn_rope: bool = True
    # An expert (routed or shared) is ``W_down (act(W_gate u) * W_up u)``;
    # False: NO gate projection, ``W_down act(W_up u)`` (no ``w_gate`` /
    # ``shared_w_gate`` leaf).
    expert_gated: bool = True
    # -- a phi4flash-shaped model (SambaY; llama arch) ----------------------
    # A Mamba-1 ("ssm1") layer and a gated memory unit ("gmu") are
    # ``ssm_expand x d_model`` wide inside (``ssm_inner``); the scan's state
    # is ``ssm_state`` a channel, its step comes up from ``ssm_dt_rank``
    # numbers a token, its convolution is ``kda_conv`` / ``ssm_conv_bias``,
    # its chunks ``ssm_chunk``.
    ssm_expand: int = 0
    ssm_dt_rank: int = 0
    # Plain attention is DIFFERENTIAL (``mixers._diff_core``): heads
    # in pairs, two softmax maps, ``a_1 - lambda a_2`` normed a pair;
    # ``lambda_init`` goes by the layer's published number (``first_layer``).
    diff_attn: bool = False
    attn_bias: bool = False         # biases on attention's four projections
    # The block norms and the final norm are LayerNorm with a weight AND a
    # bias (what the gpt2 arch always has), not RMSNorm.
    layer_norm: bool = False

    def __post_init__(self):
        # A config file's JSON gives lists: keep the config hashable.
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "layer_pattern", tuple(
            (bool(w), bool(r)) for w, r in self.layer_pattern))
        object.__setattr__(self, "layer_mixers", tuple(self.layer_mixers))

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        """The width of a head's query and key."""
        if self.kv_latent is not None:
            return self.d_head_nope + self.d_head_rope
        return self.d_head or self.d_model // self.n_heads

    @property
    def ssm_inner(self) -> int:
        """The inner width of a Mamba-1 layer and of a memory unit."""
        return self.ssm_expand * self.d_model

    @property
    def n_scan_layers(self) -> int:
        """Layers of the main stack ``params["layers"]``: all but the
        leading dense ones."""
        return self.n_layers - self.n_dense_layers

    @property
    def held_range(self) -> tuple[int, int] | None:
        """[first, end) of the experts the parameters hold, or None."""
        if self.experts_held is None:
            return None
        return moe.held_range(self.n_experts, *self.experts_held)

    @property
    def experts_here(self) -> int:
        held = self.held_range
        return self.n_experts if held is None else held[1] - held[0]

    @property
    def linear_mixer(self) -> str | None:
        """The model's LINEAR mixer ("kda", "gdn", "ssm" or "ssm1": one
        model has at most one kind), None for a model with none."""
        return next((m for m in LINEAR_MIXERS if m in self.layer_mixers),
                    None)

    @property
    def single_sublayer(self) -> bool:
        """Whether every layer is ONE sublayer (its mixer or its FFN):
        the model names a layer with no mixer."""
        return FFN_ONLY in self.layer_mixers

    def layers_with(self, sublayer: str) -> tuple[int, ...]:
        """The layers (of the ``n_layers``) that run ``sublayer``: the
        mixer of that name, or "ffn" (every layer of a model of whole
        blocks)."""
        if sublayer == FFN_ONLY and not self.single_sublayer:
            return tuple(range(self.n_layers))
        names = self.layer_mixers or ("attn",) * self.n_layers
        return tuple(i for i, m in enumerate(names) if m == sublayer)

    def layer_kind(self, i: int) -> tuple[bool, bool] | str | None:
        """The kind of layer ``i`` of the ``n_layers``: "kda", "gdn" or
        "ssm" for a linear layer, "ffn" for a layer with no mixer, else
        (windowed, rope) of its attention (latent attention: full, rotated
        or not; plain attention beside other mixers: its place in the
        ``layer_pattern`` where there is one, else full, rotated or not,
        ``attn_rope``); None with no pattern: the arch's own."""
        if self.layer_mixers and self.layer_mixers[i] != "attn":
            return self.layer_mixers[i]
        if self.kv_latent is not None:
            return (False, self.latent_rope)
        if self.layer_mixers and not self.layer_pattern:
            return (False, self.attn_rope)
        if not self.layer_pattern:
            return None
        return self.layer_pattern[((self.first_layer or 0) + i)
                                  % len(self.layer_pattern)]

    @property
    def ffn_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.arch == "llama":
            # 8/3 * d rounded up to a multiple of 256 (MXU tiling)
            return ((int(8 * self.d_model / 3) + 255) // 256) * 256
        return 4 * self.d_model

    @property
    def tied(self) -> bool:
        if self.tie_embeddings is not None:
            return self.tie_embeddings
        return self.arch == "gpt2"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def num_params(self) -> int:
        return sum(
            int(math.prod(p.shape)) for p in jax.tree.leaves(self.shapes())
        )

    def shapes(self):
        """ShapeDtypeStruct pytree of the parameters (used by init,
        partition_specs, and abstract eval without materializing)."""
        return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), self))


# -- presets ----------------------------------------------------------------

def gpt2_small(**kw) -> TransformerConfig:
    """GPT-2 124M — the reference's Ray-Train-GPT-2 north-star model
    (BASELINE.json config #2)."""
    return replace(TransformerConfig(), **kw)


def gpt2_medium(**kw) -> TransformerConfig:
    return replace(
        TransformerConfig(n_layers=24, d_model=1024, n_heads=16), **kw
    )


def gpt2_xl(**kw) -> TransformerConfig:
    return replace(
        TransformerConfig(n_layers=48, d_model=1600, n_heads=25), **kw
    )


def llama2_7b(**kw) -> TransformerConfig:
    return replace(
        TransformerConfig(
            vocab_size=32000, n_layers=32, d_model=4096, n_heads=32,
            n_kv_heads=32, d_ff=11008, max_seq_len=4096, arch="llama",
        ),
        **kw,
    )


def llama3_8b(**kw) -> TransformerConfig:
    return replace(
        TransformerConfig(
            vocab_size=128256, n_layers=32, d_model=4096, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq_len=8192, arch="llama",
            rope_theta=500000.0,
        ),
        **kw,
    )


def mistral_7b(**kw) -> TransformerConfig:
    """Mistral-7B-v0.1 geometry (GQA 8 kv-heads, 32k positions)."""
    return replace(
        TransformerConfig(
            vocab_size=32000, n_layers=32, d_model=4096, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq_len=32768, arch="llama",
        ),
        **kw,
    )


def qwen2_7b(**kw) -> TransformerConfig:
    """Qwen2-7B geometry (GQA 4 kv-heads, 1M rope theta)."""
    return replace(
        TransformerConfig(
            vocab_size=152064, n_layers=28, d_model=3584, n_heads=28,
            n_kv_heads=4, d_ff=18944, max_seq_len=32768, arch="llama",
            rope_theta=1000000.0,
        ),
        **kw,
    )


def mixtral_8x7b(**kw) -> TransformerConfig:
    """Mixtral-8x7B geometry: Mistral-7B dims with 8 experts, top-2."""
    return mistral_7b(n_experts=8, expert_top_k=2, **kw)


def olmoe_1b_7b(**kw) -> TransformerConfig:
    """OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct ``config.json``): 64
    experts of width 1024, 8 a token, dropless, gates not renormalised,
    QK-norm; balance and z weights from its recipe (arXiv:2409.02060)."""
    return replace(
        TransformerConfig(
            vocab_size=50304, n_layers=16, d_model=2048, n_heads=16,
            n_kv_heads=16, d_ff=1024, max_seq_len=4096, arch="llama",
            n_experts=64, expert_top_k=8, expert_capacity_factor=None,
            expert_norm_topk=False, router_aux_weight=0.01,
            router_z_weight=0.001, qk_norm=True,
        ),
        **kw,
    )


def smallthinker_21b_a3b(**kw) -> TransformerConfig:
    """SmallThinker-21B-A3B-Instruct (PowerInfer ``config.json``;
    arXiv:2507.20984): 52 layers in periods of four, the first global
    (full causal attention, NO positional encoding), three windowed
    (4,096 keys, RoPE theta 1.5e6); 28 query heads on 4 key / value
    heads of 128 over a 2,560-wide model; 64 ReGLU experts 768 wide, 6 a
    token, dropless, the gates a softmax over the six chosen logits; the
    router reads the block's FIRST norm. Balance weight 0.01 (Switch's;
    ``config.json`` gives none), no z term."""
    return replace(
        TransformerConfig(
            vocab_size=151936, n_layers=52, d_model=2560, n_heads=28,
            n_kv_heads=4, d_head=128, d_ff=768, max_seq_len=16384,
            arch="llama", rope_theta=1.5e6, norm_eps=1e-6,
            n_experts=64, expert_top_k=6, expert_capacity_factor=None,
            expert_norm_topk=True, router_aux_weight=0.01,
            router_z_weight=0.0, expert_activation="relu",
            router_input="attn_norm", sliding_window=4096,
            layer_pattern=((False, False), (True, True), (True, True),
                           (True, True)),
        ),
        **kw,
    )


def kanana_2_30b_a3b(**kw) -> TransformerConfig:
    """kanana-2-30b-a3b (kakaocorp ``config.json``, ``model_type``
    ``deepseek_v3``): 48 layers, the first dense (SwiGLU 6,144); latent
    attention, 32 heads of 128 + 64 (rotary, one key for all heads)
    against values of 128 from a 512-wide latent, no query latent; 128
    SwiGLU experts 768 wide, 6 a token by a sigmoid router whose bias
    only the choice sees, gates renormalised and scaled by 2.448, two
    shared experts as one SwiGLU of 1,536; no router loss term. The
    bias's rate and rule are DeepSeek-V3's (arXiv:2412.19437)."""
    return replace(
        TransformerConfig(
            vocab_size=128256, n_layers=48, d_model=2048, n_heads=32,
            d_ff=768, max_seq_len=32768, arch="llama", rope_theta=1e6,
            norm_eps=1e-6, kv_latent=512, d_head_nope=128, d_head_rope=64,
            d_head_v=128, n_dense_layers=1, d_ff_dense=6144,
            d_ff_shared=1536, n_experts=128, expert_top_k=6,
            expert_capacity_factor=None, expert_norm_topk=True,
            router_aux_weight=0.0, router_z_weight=0.0,
            router_score="sigmoid", router_bias=True, router_bias_rate=1e-3,
            expert_gate_scale=2.448,
        ),
        **kw,
    )


def kimi_linear_48b_a3b(**kw) -> TransformerConfig:
    """Kimi-Linear-48B-A3B (moonshotai ``config.json``, ``model_type``
    ``kimi_linear``; arXiv:2510.26692): 27 layers, the first dense (SwiGLU
    9,216); layers 4, 8, ..., 24 and 27 latent attention WITHOUT any
    rotation (32 heads of 128 + 64, the 64 ONE key part all heads share,
    against values of 128 from a 512-wide latent), the other twenty KDA
    (32 heads of 128, a short convolution over 4 positions); 256 SwiGLU
    experts 1,024 wide, 8 a token by a sigmoid router whose bias only
    the choice sees, gates renormalised and scaled by 2.446, one shared
    expert; no router loss term. ``n_layers=n`` takes the published
    layers 1 to n. The bias's rate and rule are DeepSeek-V3's
    (arXiv:2412.19437); the low-rank maps' rank (the head width), the
    output gate and the gates' init are the paper's and its public
    implementation's (``fla`` ``KimiDeltaAttention``)."""
    full = (4, 8, 12, 16, 20, 24, 27)
    mixers = tuple("attn" if i in full else "kda"
                   for i in range(1, kw.get("n_layers", 27) + 1))
    return replace(
        TransformerConfig(
            vocab_size=163840, n_layers=27, d_model=2304, n_heads=32,
            d_ff=1024, max_seq_len=1048576, arch="llama", norm_eps=1e-5,
            kv_latent=512, d_head_nope=128, d_head_rope=64, d_head_v=128,
            latent_rope=False, layer_mixers=mixers, kda_heads=32,
            kda_head_dim=128, kda_conv=4, n_dense_layers=1, d_ff_dense=9216,
            d_ff_shared=1024, n_experts=256, expert_top_k=8,
            expert_capacity_factor=None, expert_norm_topk=True,
            router_aux_weight=0.0, router_z_weight=0.0,
            router_score="sigmoid", router_bias=True, router_bias_rate=1e-3,
            expert_gate_scale=2.446,
        ),
        **kw,
    )


def trinity_mini_26b_a3b(**kw) -> TransformerConfig:
    """Trinity-Mini (arcee-ai ``config.json``, ``model_type`` ``afmoe``;
    the public implementation is ``transformers``' ``modeling_afmoe.py``):
    32 layers, the first two dense (SwiGLU 6,144); 32 query heads on 4 key
    / value heads of 128 over a 2,048-wide model, each head's q and k
    RMS-normed (one weight for a layer's query heads, one for its key
    heads), the kernels' output gated by ``sigmoid(W_g h)``; three layers
    of four windowed (2,048 keys, RoPE theta 1e4), the fourth global with
    NO positional encoding; a norm on each sublayer's input AND output;
    the embedding times sqrt(2,048); 128 SwiGLU experts 1,024 wide, 8 a
    token by a sigmoid router whose bias only the choice sees, gates
    renormalised and scaled by 2.826, one shared expert; no router loss
    term. ``first_layer=f, n_layers=n, n_dense_layers=k`` takes the
    published layers f to f + n - 1, the first k of them dense. The bias's
    rule is DeepSeek-V3's (arXiv:2412.19437) at ``load_balance_coeff``."""
    window, full = (True, True), (False, False)
    return replace(
        TransformerConfig(
            vocab_size=200192, n_layers=32, d_model=2048, n_heads=32,
            n_kv_heads=4, d_head=128, d_ff=1024, max_seq_len=131072,
            arch="llama", rope_theta=1e4, norm_eps=1e-5,
            sliding_window=2048, layer_pattern=(window, window, window, full),
            first_layer=0, qk_norm="head", attn_gate=True, post_norm=True,
            embed_scale=math.sqrt(2048), n_dense_layers=2, d_ff_dense=6144,
            d_ff_shared=1024, n_experts=128, expert_top_k=8,
            expert_capacity_factor=None, expert_norm_topk=True,
            router_aux_weight=0.0, router_z_weight=0.0,
            router_score="sigmoid", router_bias=True, router_bias_rate=1e-3,
            expert_gate_scale=2.826,
        ),
        **kw,
    )


def qwen3_next_80b_a3b(**kw) -> TransformerConfig:
    """Qwen3-Next-80B-A3B (Qwen ``config.json``, ``model_type``
    ``qwen3_next``; the public implementation is ``transformers``'
    ``modeling_qwen3_next.py``): 48 layers over a 2,048-wide stream, layer
    ``i`` (from 0) gated softmax attention where ``(i + 1) % 4 == 0`` (16
    query heads on 2 key / value heads of 256, q and k RMS-normed a head,
    RoPE theta 1e7 on the first 64 of a head's 256 values, the output
    times ``sigmoid`` of a gate projected beside q), every other layer
    Gated DeltaNet (16 query / key heads under 32 value heads of 128, a
    short convolution over 4 positions, ONE log-decay a value head, the
    head-normed output times ``silu(z)``); every norm but DeltaNet's
    output norm zero-centred; every layer 512 SwiGLU experts 512 wide, 10
    a token by a softmax router, gates renormalised, beside one shared
    expert 512 wide times ``sigmoid(w_s . h)``; load-balance weight 0.001
    (``Qwen3NextConfig``'s ``router_aux_loss_coef``), no z term.
    ``n_layers=n`` takes the published layers 0 to n - 1. The published
    multi-token-prediction module is not here (``lm_loss`` has one head)."""
    mixers = tuple("attn" if (i + 1) % 4 == 0 else "gdn"
                   for i in range(kw.get("n_layers", 48)))
    return replace(
        TransformerConfig(
            vocab_size=151936, n_layers=48, d_model=2048, n_heads=16,
            n_kv_heads=2, d_head=256, d_ff=512, max_seq_len=262144,
            arch="llama", rope_theta=1e7, rope_fraction=0.25, norm_eps=1e-6,
            norm_zero_centred=True, qk_norm="head", attn_gate=True,
            layer_mixers=mixers, kda_heads=32, kda_head_dim=128, kda_conv=4,
            linear_key_heads=16, d_ff_shared=512, shared_expert_gate=True,
            n_experts=512, expert_top_k=10, expert_capacity_factor=None,
            expert_norm_topk=True, router_aux_weight=0.001,
            router_z_weight=0.0,
        ),
        **kw,
    )


NEMOTRON_3_NANO_LAYERS = (
    "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")


def nemotron_3_nano_30b_a3b(**kw) -> TransformerConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (nvidia ``config.json``,
    ``model_type`` ``nemotron_h``; arXiv:2504.03624; the public
    implementation of its mixer is ``transformers``' ``models/bamba`` /
    ``mamba2`` ``torch_forward``, of its router ``models/deepseek_v3``): 52
    layers over a 2,688-wide stream, EACH ONE sublayer behind one RMSNorm,
    by ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer (64 heads of 64,
    state 128, ``B`` / ``C`` in 8 groups, a convolution over 4 positions
    with a bias, chunks of 128, the output gated by ``silu(z)`` AHEAD of an
    RMS norm over 8 groups of 512 channels), ``*`` attention (32 query
    heads on 2 key / value heads of 128, NO positional encoding), ``E``
    experts (128 of ``W_down relu(W_up u)^2`` 1,856 wide, no gate
    projection, 6 a token by a sigmoid router whose bias only the choice
    sees, gates renormalised and scaled by 2.5, one shared expert of the
    same form 3,712 wide); no router loss term. ``n_layers=n`` takes the
    published layers 0 to n - 1. The bias's rate and rule are DeepSeek-V3's
    (arXiv:2412.19437); ``rescale_prenorm_residual`` (an init scale) is
    not applied."""
    names = {"M": "ssm", "E": FFN_ONLY, "*": "attn"}
    mixers = tuple(names[kind] for kind
                   in NEMOTRON_3_NANO_LAYERS[:kw.get("n_layers", 52)])
    return replace(
        TransformerConfig(
            vocab_size=131072, n_layers=52, d_model=2688, n_heads=32,
            n_kv_heads=2, d_head=128, d_ff=1856, max_seq_len=262144,
            arch="llama", norm_eps=1e-5, attn_rope=False,
            layer_mixers=mixers, kda_heads=64, kda_head_dim=64, kda_conv=4,
            ssm_state=128, ssm_groups=8, ssm_chunk=128, d_ff_shared=3712,
            expert_gated=False, expert_activation="relu2", n_experts=128,
            expert_top_k=6, expert_capacity_factor=None,
            expert_norm_topk=True, router_aux_weight=0.0,
            router_z_weight=0.0, router_score="sigmoid", router_bias=True,
            router_bias_rate=1e-3, expert_gate_scale=2.5,
        ),
        **kw,
    )


def phi4_mini_flash_reasoning(**kw) -> TransformerConfig:
    """Phi-4-mini-flash-reasoning (microsoft ``config.json``, ``model_type``
    ``phi4flash``; the architecture is SambaY, arXiv:2507.06607; the public
    implementation is the repository's ``modeling_phi4flash.py``): 32 layers
    over a 2,560-wide stream, each ``x <- x + mixer(LN(x))``, ``x <- x +
    MLP(LN(x))``, LayerNorm with weight and bias (eps 1e-5), the MLP a SwiGLU
    of 10,240 (the published ``[gate | up]`` matrix as two leaves), tied
    embedding and head, NO positional encoding. The mixer by published layer
    ``l`` (from 0): ``l`` even and <= 16 a Mamba-1 selective scan (arXiv:
    2312.00752: inner width 5,120, state 16, a convolution over 4 positions
    with a bias, ``dt_rank`` 160; layer 16 also hands out its scan output
    ``y``, the memory); ``l`` odd and <= 15 differential attention
    (arXiv:2410.05258) over a window of 512 keys, ``l`` = 17 the same, full
    causal, which also hands out its keys and values (40 query and 20 key /
    value heads of 64, in interleaved pairs, biases on all four
    projections); ``l`` even and >= 18 a gated memory unit ``W_2 (m *
    silu(W_1 h))`` on layer 16's ``y``; ``l`` odd and >= 19 differential
    cross-attention, its own queries against layer 17's keys and values.
    ``first_layer=f, n_layers=n`` takes the published layers f to f + n - 1
    (``lambda_init`` goes by the published number); default all 32, 3.85 B
    parameters. NOT in ``config.json`` and taken from the implementation:
    the four Mamba sizes (its defaults: state 16, convolution 4, expand 2,
    ``dt_rank`` ceil(2,560 / 16)), the biases on ``Wqkv`` / ``out_proj``
    (``nn.Linear(..., bias=True)``) and their absence on the Mamba and
    memory-unit projections, the differential form with ``lambda_init(l) =
    0.8 - 0.6 exp(-0.3 l)`` and its RMS norm over a pair's 128 channels,
    heads paired interleaved (pair n is heads 2 n and 2 n + 1), the window
    counted with the query's own position, that nothing is rotated."""
    first = kw.get("first_layer", 0)
    n = kw.get("n_layers", 32 - first)

    def mixer(l: int) -> str:
        if l % 2 == 0:
            return "ssm1" if l <= 16 else "gmu"
        return "attn" if l <= 17 else "cross"

    return replace(
        TransformerConfig(
            vocab_size=200064, d_model=2560, n_heads=40, n_kv_heads=20,
            d_head=64, d_ff=10240, max_seq_len=262144, arch="llama",
            norm_eps=1e-5, tie_embeddings=True, layer_norm=True,
            attn_rope=False, diff_attn=True, attn_bias=True,
            sliding_window=512, first_layer=0,
            layer_pattern=tuple((l % 2 == 1 and l <= 15, False)
                                for l in range(32)),
            ssm_expand=2, ssm_state=16, ssm_dt_rank=160, kda_conv=4,
            ssm_chunk=128,
        ),
        **{**kw, "n_layers": n,
           "layer_mixers": tuple(mixer(l) for l in range(first, first + n))},
    )


def moe_small(**kw) -> TransformerConfig:
    """Mixtral-style MoE on the small-llama geometry: 8 experts, top-2.
    Per-token FLOPs ≈ dense small; total params ≈ 8× the FFN stack."""
    defaults = dict(
        vocab_size=32000, n_layers=12, d_model=768, n_heads=12,
        max_seq_len=2048, arch="llama", n_experts=8, expert_top_k=2,
    )
    defaults.update(kw)
    return TransformerConfig(**defaults)


def tiny_moe(**kw) -> TransformerConfig:
    defaults = dict(
        vocab_size=256, n_layers=2, d_model=64, n_heads=4, max_seq_len=128,
        arch="llama", n_experts=4, expert_top_k=2,
    )
    defaults.update(kw)
    return TransformerConfig(**defaults)


def tiny(**kw) -> TransformerConfig:
    """Test-sized model (CI on the 8-device CPU mesh)."""
    return replace(
        TransformerConfig(
            vocab_size=256, n_layers=2, d_model=64, n_heads=4,
            max_seq_len=128, remat=False,
        ),
        **kw,
    )


# -- init -------------------------------------------------------------------

def _check_config(c: TransformerConfig) -> None:
    """Refuse, by name, a combination the program does not run."""
    if c.n_experts > 0 and c.arch != "llama":
        raise ValueError("MoE (n_experts > 0) requires arch='llama'")
    if c.qk_norm not in (False, True, "head"):
        raise ValueError("qk_norm must be False, True (all heads together) "
                         "or 'head'")
    if c.qk_norm and c.arch != "llama":
        raise ValueError("qk_norm requires arch='llama'")
    patterned = (c.layer_pattern or c.sliding_window is not None
                 or c.d_head is not None)
    if patterned and c.arch != "llama":
        raise ValueError("layer_pattern, sliding_window and d_head require "
                         "arch='llama'")
    if c.first_layer is not None and (not c.layer_pattern
                                      or c.first_layer < 0):
        raise ValueError(
            "first_layer is the published number (>= 0) of the first layer "
            f"of a layer_pattern: got first_layer={c.first_layer} with "
            f"layer_pattern={c.layer_pattern}")
    if (c.layer_pattern and c.first_layer is None
            and c.n_layers % len(c.layer_pattern)):
        raise ValueError(
            f"n_layers={c.n_layers} is not a whole number of periods of "
            f"{len(c.layer_pattern)} layers (layer_pattern; first_layer "
            f"anchors a pattern that starts or stops mid-period)")
    for name, wrong in (("attn_gate", c.attn_gate),
                        ("post_norm", c.post_norm),
                        ("diff_attn", c.diff_attn),
                        ("attn_bias", c.attn_bias),
                        ("layer_norm", c.layer_norm)):
        if wrong and c.arch != "llama":
            raise ValueError(f"{name} requires arch='llama'")
    if c.diff_attn:
        for name, wrong in (
                ("kv_latent", c.kv_latent is not None),
                ("qk_norm", c.qk_norm), ("attn_gate", c.attn_gate),
                ("heads it rotates (give layer_mixers with attn_rope=False "
                 "or a layer_pattern of unrotated kinds)",
                 any((c.layer_kind(i) or (False, True))[1]
                     for i in c.layers_with("attn"))),
                (f"an odd number of heads (n_heads {c.n_heads}, kv_heads "
                 f"{c.kv_heads}): they pair up",
                 c.n_heads % 2 or c.kv_heads % 2)):
            if wrong:
                raise ValueError(
                    f"differential attention (diff_attn) does not run with "
                    f"{name}")
    if c.attn_bias and not c.diff_attn:
        raise ValueError("attn_bias is read by differential attention "
                         "(diff_attn) alone: plain and latent attention "
                         "project without a bias")
    if c.attn_gate and c.kv_latent is not None:
        raise ValueError("attn_gate gates plain attention's output: it does "
                         "not run with kv_latent")
    for name, wrong in (("norm_zero_centred", c.norm_zero_centred),
                        ("shared_expert_gate", c.shared_expert_gate),
                        ("rope_fraction", c.rope_fraction != 1.0)):
        if wrong and c.arch != "llama":
            raise ValueError(f"{name} requires arch='llama'")
    rotated = c.head_dim * c.rope_fraction
    if c.rope_fraction != 1.0 and (
            not 0.0 < c.rope_fraction < 1.0 or rotated != int(rotated)
            or int(rotated) % 2 or c.kv_latent is not None):
        raise ValueError(
            f"rope_fraction={c.rope_fraction} has to leave plain attention "
            f"an even whole number of a head's {c.head_dim} values to "
            f"rotate (latent attention rotates its d_head_rope)")
    if c.shared_expert_gate and not c.d_ff_shared:
        raise ValueError("shared_expert_gate gates the shared expert: set "
                         "d_ff_shared")
    windowed = any(w for w, _ in c.layer_pattern)
    if windowed != (c.sliding_window is not None):
        raise ValueError(
            "sliding_window is the width of the layer_pattern's windowed "
            f"positions: got sliding_window={c.sliding_window} with "
            f"layer_pattern={c.layer_pattern}")
    if c.expert_activation not in moe.ACTIVATIONS:
        raise ValueError(f"expert_activation must be one of "
                         f"{sorted(moe.ACTIVATIONS)}")
    if c.router_input not in ("mlp_norm", "attn_norm"):
        raise ValueError("router_input must be 'mlp_norm' or 'attn_norm'")
    if c.router_score not in moe.ROUTER_SCORES:
        raise ValueError(f"router_score must be one of {moe.ROUTER_SCORES}")
    asks_dropless = (c.experts_held is not None
                     or c.router_input != "mlp_norm"
                     or c.expert_activation != "silu"
                     or c.router_score != "softmax" or c.router_bias
                     or c.expert_gate_scale != 1.0 or c.d_ff_shared
                     or not c.expert_gated)
    if asks_dropless and (c.n_experts == 0
                          or c.expert_capacity_factor is not None):
        raise ValueError(
            "experts_held, router_input='attn_norm', a ReGLU or relu2 "
            "expert_activation, router_score='sigmoid', router_bias, "
            "expert_gate_scale, d_ff_shared and expert_gated=False are the "
            "dropless path's (n_experts > 0, expert_capacity_factor=None)")
    if not c.expert_gated and c.shared_expert_gate:
        raise ValueError("shared_expert_gate does not run with experts "
                         "that have no gate projection (expert_gated)")
    if c.router_bias_rate and not c.router_bias:
        raise ValueError("router_bias_rate moves the router_bias: set it")
    if c.kv_latent is not None:
        for name, wrong in (
                ("arch != 'llama'", c.arch != "llama"),
                ("qk_norm", c.qk_norm),
                ("a layer_pattern", bool(c.layer_pattern)),
                ("GQA (n_kv_heads < n_heads)", c.kv_heads != c.n_heads)):
            if wrong:
                raise ValueError(
                    f"latent attention (kv_latent) does not run with {name}")
        if min(c.kv_latent, c.d_head_nope, c.d_head_rope, c.d_head_v) < 1 \
                or c.d_head_rope % 2:
            raise ValueError(
                "latent attention needs kv_latent, d_head_nope, d_head_v >= "
                "1 and an even d_head_rope >= 2")
    if not c.latent_rope and c.kv_latent is None:
        raise ValueError("latent_rope=False describes latent attention "
                         "(kv_latent)")
    if c.linear_key_heads is not None and (
            "gdn" not in c.layer_mixers or c.linear_key_heads < 1
            or c.kda_heads % c.linear_key_heads):
        raise ValueError(
            f"linear_key_heads={c.linear_key_heads} are the query / key "
            f"heads of a model with 'gdn' layer_mixers and divide its "
            f"kda_heads={c.kda_heads}")
    if not c.attn_rope and (not c.layer_mixers or c.kv_latent is not None):
        raise ValueError("attn_rope=False describes plain attention beside "
                         "layer_mixers (a layer_pattern says it a position, "
                         "latent_rope for latent attention)")
    if c.layer_mixers:
        names = tuple(mixers.MIXERS) + (FFN_ONLY,)
        checks = {name: mixers.MIXERS[name].check(c)
                  for name in mixers.MIXERS if name in c.layer_mixers}
        # a layer whose kind has no stack to take its leaves from, by index
        needs = {FFN_ONLY: ("an FFN of experts alone (n_experts > 0, no "
                            "n_dense_layers, no post_norm, router_input "
                            "'mlp_norm')",
                            c.n_experts > 0 and not c.n_dense_layers
                            and not c.post_norm
                            and c.router_input == "mlp_norm"),
                 **{name: need for name, (need, _) in checks.items()
                    if need is not None}}
        for i, name in enumerate(c.layer_mixers):
            if name in needs and not needs[name][1]:
                raise ValueError(
                    f"layer_mixers[{i}] = {name!r} needs {needs[name][0]}")
        for name, wrong in (
                (f"names other than {names}",
                 not set(c.layer_mixers) <= set(names)),
                (f"{len(c.layer_mixers)} names for n_layers={c.n_layers}",
                 len(c.layer_mixers) != c.n_layers),
                ("a layer_pattern that no first_layer anchors (it gives the "
                 "attention layers' kinds by their published numbers)",
                 bool(c.layer_pattern) and c.first_layer is None),
                # what each of the model's mixers does not run with
                *(row for _, rows in checks.values() for row in rows),
                ("more than one kind of linear mixer in one model",
                 len(set(LINEAR_MIXERS) & set(c.layer_mixers)) > 1)):
            if wrong:
                raise ValueError(f"layer_mixers does not run with {name}")
        # a reader of a memory no layer ahead of it writes, by index
        written = set()
        for i, name in enumerate(c.layer_mixers):
            mixer = mixers.MIXERS.get(name)
            if mixer is None:               # "ffn": no mixer
                continue
            if mixer.reads not in (None, *written):
                writers = [k for k, m in mixers.MIXERS.items()
                           if m.writes == mixer.reads]
                raise ValueError(
                    f"layer_mixers[{i}] = {name!r} reads the memory "
                    f"{mixer.reads!r}, which no layer ahead of it hands out "
                    f"(a layer named one of {writers})")
            written.add(mixer.writes)
    if c.n_dense_layers:
        unanchored = bool(c.layer_pattern) and c.first_layer is None
        if (c.n_experts == 0 or unanchored or c.d_ff_dense is None
                or not 0 < c.n_dense_layers < c.n_layers
                or not c.expert_gated):
            raise ValueError(
                "n_dense_layers are the first of an expert model's n_layers "
                "(n_experts > 0, no layer_pattern that first_layer does not "
                "anchor, 0 < n_dense_layers < n_layers, expert_gated) and "
                "need their FFN width d_ff_dense")
    if c.experts_held is not None:      # raises where they do not divide
        moe.held_range(c.n_experts, *c.experts_held)


def init_params(rng, config: TransformerConfig):
    """Initialize the parameter pytree.

    Layer params carry a leading [n_layers] axis (consumed by lax.scan).
    GPT-2 init: N(0, 0.02), residual-out projections scaled by
    1/sqrt(2*n_layers). A model with leading dense layers has two
    stacks: ``dense_layers`` [n_dense_layers, ...] and ``layers`` (the
    expert layers, [n_layers - n_dense_layers, ...]). With
    ``layer_mixers`` a stack's ``attn`` holds ``wo`` (``bo``) alone, of
    every layer whose mixer has attention's inner width (a record with
    ``own_out`` keeps its own output projection); a mixer's own leaves
    (``kda`` / ``gdn`` / ``ssm`` / ``ssm1`` / ``gmu`` / ``cross``,
    attention's ``mla`` / ``mha``) are stacked over the layers of that kind
    and drawn by its record (``mixers.MIXERS``: ``init``, where each one's
    leaves are described). A zero-centred norm's weight is made 0; with
    ``layer_norm`` the stream's norms have a bias ``b``, made 0.
    In a model of single-sublayer blocks a layer holds its own sublayer's
    leaves alone (``_holds``); experts with no gate projection have no
    ``w_gate`` / ``shared_w_gate`` leaf.
    """
    c = config
    _check_config(c)
    pdt = jnp.dtype(c.param_dtype)
    L, D, H, Dh, F = (
        c.n_scan_layers, c.d_model, c.n_heads, c.head_dim, c.ffn_dim)
    std = 0.02
    res_std = std / math.sqrt(2 * c.n_layers)
    keys = iter(jax.random.split(rng, 16))
    # What a DeepSeek-V3-shaped model adds draws from keys of its own, so
    # every other model's weights stay what the seed always gave.
    more = iter(jax.random.split(jax.random.fold_in(rng, 1), 16))
    # ... and so does what a model with ``layer_mixers`` adds.
    third = iter(jax.random.split(jax.random.fold_in(rng, 2), 32))
    # ... and the gate on attention's output.
    fourth = iter(jax.random.split(jax.random.fold_in(rng, 3), 2))

    def norm(key, *shape, s=std):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)

    def uniform(key, *shape, low, high):
        return jax.random.uniform(key, shape, jnp.float32, low, high)

    def unit(*shape):
        """A norm's weight at its start: it scales by 1."""
        return (jnp.zeros if c.norm_zero_centred else jnp.ones)(shape, pdt)

    draw = mixers.Draw(norm=norm, uniform=uniform, unit=unit,
                       res_std=res_std, gate_keys=fourth)

    def mixer_stacks(keys, first, n):
        """The token mixers' leaves of the ``n`` layers from ``first``:
        each kind's from its record (``mixers.MIXERS``), attention's from
        the stack's own keys and any other's from ``third``."""
        attn = mixers.MIXERS["attn"]
        if not c.layer_mixers:
            return {"attn": attn.init(c, keys, n, draw)}
        here = c.layer_mixers[first:first + n]
        stacks = {}
        for kind, mixer in mixers.MIXERS.items():
            if kind in here:
                stacks[mixer.stack(c)] = mixer.init(
                    c, keys if mixer is attn else third, here.count(kind),
                    draw)
        # ``attn/wo`` (``bo``) is ONE stack over every layer whose mixer
        # has attention's inner width, whatever the mixer; a record with
        # ``own_out`` has an inner width, and an output projection, of its
        # own
        for name in ("wo", "bo"):
            stacks.get(attn.stack(c), {}).pop(name, None)
        value = c.d_head_v if c.kv_latent is not None else Dh
        rows = sum(kind != FFN_ONLY and not mixers.MIXERS[kind].own_out
                   for kind in here)
        if rows:
            stacks["attn"] = {"wo": norm(next(third), rows, H, value, D,
                                         s=res_std)}
            if c.attn_bias:
                stacks["attn"]["bo"] = norm(next(third), rows, D)
        return stacks

    def stream_norm(*shape):
        """A norm of the stream: a weight, and with ``layer_norm`` a bias."""
        return {"w": unit(*shape),
                **({"b": jnp.zeros(shape, pdt)} if c.layer_norm else {})}

    def block_norms(n):
        names = ("ln1", "ln2") + (("ln1_post", "ln2_post") if c.post_norm
                                  else ())
        if c.single_sublayer:       # a layer has ONE norm: its sublayer's
            n_ffn = len(c.layers_with("ffn"))
            return {"ln1": stream_norm(n - n_ffn, D),
                    "ln2": stream_norm(n_ffn, D)}
        return {name: stream_norm(n, D) if name in ("ln1", "ln2")
                else {"w": unit(n, D)} for name in names}

    def ffn_stack(keys, n, width):
        return {
            **({"w_gate": norm(next(keys), n, D, width)}
               if c.expert_gated else {}),
            "w_up": norm(next(keys), n, D, width),
            "w_down": norm(next(keys), n, width, D, s=res_std),
        }

    params = {
        "embed": {"tokens": norm(next(keys), c.vocab_size, D)},
        "layers": mixer_stacks(keys, c.n_dense_layers, L),
        "final_norm": stream_norm(D),
    }
    if c.arch == "gpt2":
        params["embed"]["pos"] = norm(next(keys), c.max_seq_len, D)
        params["layers"]["ln1"] = {
            "w": jnp.ones((L, D), pdt), "b": jnp.zeros((L, D), pdt)
        }
        params["layers"]["ln2"] = {
            "w": jnp.ones((L, D), pdt), "b": jnp.zeros((L, D), pdt)
        }
        params["layers"]["mlp"] = {
            "w_in": norm(next(keys), L, D, F),
            "b_in": jnp.zeros((L, F), pdt),
            "w_out": norm(next(keys), L, F, D, s=res_std),
            "b_out": jnp.zeros((L, D), pdt),
        }
        params["final_norm"]["b"] = jnp.zeros((D,), pdt)
    else:
        params["layers"].update(block_norms(L))
        if c.n_experts > 0:
            E = c.experts_here
            if c.single_sublayer:       # the FFN layers' leaves alone
                L = len(c.layers_with("ffn"))
            params["layers"]["router"] = {
                "w": norm(next(keys), L, D, c.n_experts)}
            params["layers"]["mlp"] = {
                **({"w_gate": norm(next(keys), L, E, D, F)}
                   if c.expert_gated else {}),
                "w_up": norm(next(keys), L, E, D, F),
                "w_down": norm(next(keys), L, E, F, D, s=res_std),
            }
            if c.router_bias:
                # Seeded like a matrix, not zeros: with zeros a program
                # that let the bias into the gates would compute what a
                # sound one computes.
                params["layers"]["router"]["b"] = norm(
                    next(more), L, c.n_experts)
            if c.d_ff_shared:
                shared = ffn_stack(more, L, c.d_ff_shared)
                params["layers"]["mlp"].update(
                    {f"shared_{name}": w for name, w in shared.items()})
                if c.shared_expert_gate:
                    params["layers"]["mlp"]["shared_gate"] = norm(
                        next(more), L, D)
        else:
            params["layers"]["mlp"] = ffn_stack(keys, L, F)
        if c.n_dense_layers:
            n = c.n_dense_layers
            params["dense_layers"] = {
                **mixer_stacks(more, 0, n), **block_norms(n),
                "mlp": ffn_stack(more, n, c.d_ff_dense),
            }
    if not c.tied:
        params["lm_head"] = norm(next(keys), D, c.vocab_size)
    return params


# -- partitioning -----------------------------------------------------------

def partition_specs(config: TransformerConfig):
    """Megatron-style TP base specs mirroring the param tree.

    Heads / ffn-hidden shard over the ``tensor`` axis so each attention
    and MLP block is a pair of column→row parallel matmuls (one psum per
    block, inserted by GSPMD). Vocab shards over ``tensor`` in the
    embedding/head. FSDP is layered on top by infer_param_specs.
    """
    c = config
    ffn = {
        "w_gate": P(None, None, AXIS_TENSOR),
        "w_up": P(None, None, AXIS_TENSOR),
        "w_down": P(None, AXIS_TENSOR, None),
    }
    # every mixer's own leaves by its record, under the name of its stack;
    # ``attn`` (every mixer layer's ``wo``) by attention's
    stacks = {"attn": mixers.MIXERS["attn"].specs(c),
              **{mixer.stack(c): mixer.specs(c)
                 for mixer in mixers.MIXERS.values()}}
    specs = {
        "embed": {"tokens": P(AXIS_TENSOR, None)},
        "layers": {**stacks, "ln1": None, "ln2": None},
        "dense_layers": {**stacks, "ln1": None, "ln2": None, "mlp": ffn},
        "final_norm": None,
    }
    if c.arch == "gpt2":
        specs["embed"]["pos"] = P(None, None)
        specs["layers"]["mlp"] = {
            "w_in": P(None, None, AXIS_TENSOR),
            "b_in": P(None, AXIS_TENSOR),
            "w_out": P(None, AXIS_TENSOR, None),
            "b_out": None,
        }
    elif c.n_experts > 0:
        specs["layers"]["router"] = {"w": P(None, None, None)}
        specs["layers"]["mlp"] = {
            "w_gate": P(None, AXIS_EXPERT, None, AXIS_TENSOR),
            "w_up": P(None, AXIS_EXPERT, None, AXIS_TENSOR),
            "w_down": P(None, AXIS_EXPERT, AXIS_TENSOR, None),
            **{f"shared_{name}": spec for name, spec in ffn.items()},
        }
    else:
        specs["layers"]["mlp"] = ffn
    if not c.tied:
        specs["lm_head"] = P(None, AXIS_TENSOR)
    # Expand None-marked subtrees to per-leaf None specs.
    return _mirror(specs, config.shapes())


def _mirror(specs, shapes):
    """Expand a spec tree with None-subtree shorthands to exactly mirror
    the param tree structure."""
    if isinstance(shapes, dict):
        out = {}
        for k, sub in shapes.items():
            s = specs.get(k) if isinstance(specs, dict) else None
            out[k] = _mirror(s, sub)
        return out
    return specs  # leaf: a PartitionSpec or None


# -- forward ----------------------------------------------------------------

# A stack of a few LARGE layers runs unrolled, as ONE scan step
# (``lax.scan``'s ``unroll``): around a ``while`` loop XLA keeps whole-stack
# temporaries (the casts of the stacked weights hoisted out of the loop,
# the stacked gradients beside the optimizer's) that layers laid out in
# line do not need (kanana-2's cell: 16.58 GB as a loop, 12.25 GB in line,
# by the compiler's account). Both bounds are what can be seen at trace
# time: at most this many steps (longer stacks keep the loop: compile time
# is O(1) in depth there), and at least this many bytes of parameters in
# the stack (below it the temporaries are small change).
_SCAN_UNROLL_MOST = 4
_SCAN_UNROLL_BYTES = 2 ** 30


def _scan_unroll(stack, steps: int) -> int:
    """Steps of the scan over ``stack`` that run in line (``unroll``)."""
    size = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(stack))
    large = 1 < steps <= _SCAN_UNROLL_MOST and size >= _SCAN_UNROLL_BYTES
    return steps if large else 1


def _scan_layers(body, x, stack, steps: int):
    """``lax.scan`` of ``body`` over the leading axis of ``stack``."""
    return jax.lax.scan(body, x, stack, unroll=_scan_unroll(stack, steps))


def _period(kinds: tuple) -> int:
    """The layers a scan step holds: the shortest period the kinds repeat
    with at least twice (the layers left after the last whole period are
    run behind the scan); kinds that repeat with none are one period."""
    n = len(kinds)
    for p in range(1, n // 2 + 1):
        if all(kinds[i] == kinds[i % p] for i in range(n // p * p)):
            return p
    return n


def _holds(c: TransformerConfig, name: str, kind) -> bool:
    """Whether a layer of kind ``kind`` (``layer_kind``) has leaves in the
    subtree ``name`` of its stack. A mixer's own subtree (its record's
    ``stack``) is stacked over the layers of that mixer alone;
    ``attn`` (``wo``) over the layers whose mixer has no output projection
    of its own (``own_out``); in a model of single-sublayer blocks ``ln1``
    and ``attn`` over the layers that are a mixer, everything else
    (``ln2``, the router, ``mlp``) over those that are an FFN; any other
    subtree over all the layers."""
    if not c.layer_mixers:
        return True
    mixer = kind if isinstance(kind, str) else "attn"
    own = {mixers.MIXERS[m].stack(c): m for m in mixers.MIXERS
           if m in c.layer_mixers}
    if name in own:
        return own[name] == mixer
    if name == "attn" and mixer != FFN_ONLY and mixers.MIXERS[mixer].own_out:
        return False
    if not c.single_sublayer:
        return True
    return (name in ("ln1", "attn")) == (mixer != FFN_ONLY)


def _split_stack(c: TransformerConfig, stack, kinds: tuple, period: int):
    """A stack's leaves as (whole periods [periods, layers that hold the
    leaf a period, ...], the layers left after them) (``_holds``)."""
    periods = len(kinds) // period

    def split(name, sub):
        n = sum(_holds(c, name, kind) for kind in kinds[:period])
        return (jax.tree.map(lambda a: a[:periods * n].reshape(
                    periods, n, *a.shape[1:]), sub),
                jax.tree.map(lambda a: a[periods * n:], sub))

    both = {name: split(name, sub) for name, sub in stack.items()}
    return ({name: b[0] for name, b in both.items()},
            {name: b[1] for name, b in both.items()})


def _take_layer(c: TransformerConfig, stack, kinds: tuple, i: int):
    """Layer ``i``'s parameters out of a stack (or a slice of one) whose
    layers have the kinds ``kinds``: of every subtree the layer holds
    (``_holds``), its place among the layers that hold it."""
    if not c.layer_mixers:
        return jax.tree.map(lambda a: a[i], stack)
    return {name: jax.tree.map(
                lambda a, at=sum(_holds(c, name, kind)
                                 for kind in kinds[:i]): a[at], sub)
            for name, sub in stack.items() if _holds(c, name, kinds[i])}


def _writes(c: TransformerConfig) -> tuple:
    """For every layer (of ``n_layers``) the names of the memories it hands
    out: of each memory some layer's record ``reads``, the LAST layer whose
    record ``writes`` it ahead of the FIRST reader (``_check_config`` has
    seen that there is one). Empty tuples for a model with no reader."""
    names = c.layer_mixers or ("attn",) * c.n_layers
    records = [mixers.MIXERS.get(name) for name in names]
    out = [()] * c.n_layers
    for memory in sorted({r.reads for r in records if r and r.reads}):
        reader = next(i for i, r in enumerate(records)
                      if r and r.reads == memory)
        writer = max(i for i, r in enumerate(records[:reader])
                     if r and r.writes == memory)
        out[writer] += (memory,)
    return tuple(out)


def forward(params, tokens, config: TransformerConfig, *, mesh=None,
            positions=None, return_aux: bool = False,
            return_hidden: bool = False):
    """Logits for ``tokens`` [B, T] → [B, T, vocab] (float32).

    ``mesh`` adds with_sharding_constraint annotations on activations
    (batch over data+fsdp, heads/ffn over tensor); pass None outside pjit.
    ``return_aux`` additionally returns the step's statistics, every
    counter the model's layers report (``_counters``) folded over the
    layers under its ``metric`` name (``_fold``: the router's loss terms as
    means over the expert layers, the fullest layer's ``moe_load_max``,
    ``moe_expert_counts`` [expert layers, experts], ``kda_log_decay_min``
    the most negative cumulative log-decay inside any chunk of any linear
    layer, ...; none for a dense model of plain attention), and under
    ``"layers"`` every layer's own values as the main stack's scan
    stacked them ({a counter's ``key``: [layers or periods, ...]}; a layer
    that has no such sublayer reads zeros).
    ``return_hidden`` skips
    the LM head and returns the final
    normed hidden states [B, T, D] (``lm_loss`` applies the head itself,
    inside its loss).
    """
    c = config
    dt = c.compute_dtype
    B, T = tokens.shape
    _check_config(c)
    if (c.n_experts > 0 and c.expert_capacity_factor is None
            and mesh is not None and mesh.shape.get(AXIS_EXPERT, 1) > 1):
        raise NotImplementedError(
            "dropless MoE (expert_capacity_factor=None) keeps every expert "
            "on the chip: it has no all-to-all over the mesh's 'expert' "
            "axis yet (ROADMAP B3); give expert_capacity_factor a number")

    def con(x, *spec):
        return constrain(x, mesh, *spec) if mesh is not None else x

    with jax.named_scope("embed"):
        x = params["embed"]["tokens"][tokens]
        if c.embed_scale != 1.0:        # in the parameters' precision
            x = x * jnp.asarray(c.embed_scale, x.dtype)
        x = x.astype(dt)
        if c.arch == "gpt2":
            if positions is None:
                pos_emb = params["embed"]["pos"][:T]
            else:
                pos_emb = params["embed"]["pos"][positions]
            x = x + pos_emb.astype(dt)
            rope = None
        elif not c.attn_rope or (c.kv_latent is not None
                                 and not c.latent_rope):
            rope = None                 # no layer rotates anything
        else:
            cos, sin = rope_frequencies(
                int(c.head_dim * c.rope_fraction) if c.kv_latent is None
                else c.d_head_rope, c.max_seq_len, theta=c.rope_theta)
            rope = (cos, sin)
        x = con(x, _BATCH, AXIS_SEQUENCE, None)

    writes = _writes(c)
    # A model that hands out memory runs its layers IN LINE (``run_stack``),
    # and there the stream is cut between a block's halves (``_block``).
    cut = any(writes)

    def layer_of(kind, dense: bool = False, writes: tuple = ()):
        """The block of one kind of layer (static), under remat: (x, its
        leaves, {a memory's name: what an earlier layer handed out}, its
        published number or None) -> (x, its counters, the memories named
        in ``writes``, which it hands out). A memory is an INPUT of every
        layer that reads it and an output of the one that writes it: kept
        once, never made again for a reader."""
        def layer(x, lp, memory, number):
            return _block(x, lp, c, rope=rope, con=con, positions=positions,
                          kind=kind, dense=dense, memory=memory,
                          writes=writes, number=number, cut=cut)

        if not c.remat:
            return layer
        # Whatever else is recomputed, the Pallas attention kernel's output
        # and logsumexp are kept: its backward needs exactly these two, and
        # the recompute would launch the forward kernel again for them. A
        # layer whose attention took another path has no such value, and
        # the policy saves nothing more than it did.
        policies = jax.checkpoint_policies
        policy = policies.save_only_these_names(FLASH_OUT_NAME,
                                                FLASH_LSE_NAME)
        if c.remat_policy == "dots":
            policy = policies.save_from_both_policies(
                policies.dots_with_no_batch_dims_saveable, policy)
        return jax.checkpoint(layer, policy=policy)

    def run_stack(x, stack, first: int, n: int, memory: dict,
                  dense: bool = False):
        """Layers ``first`` to ``first + n`` of the model, whose
        parameters are ``stack`` -> (x, every layer's aux, stacked, the
        memories handed out so far). A layer that hands out a memory is a
        kind of its own (no period repeats it) and runs IN LINE; a scan's
        steps read the memories written ahead of it and write none."""
        kinds = tuple(c.layer_kind(first + i) for i in range(n))
        roles = writes[first:first + n]
        period = _period(tuple(zip(kinds, roles)) if any(roles) else kinds)
        layers = [layer_of(kind, dense, role)
                  for kind, role in zip(kinds, roles)]
        # the layers' PUBLISHED numbers, for a model that reads them
        start = (c.first_layer or 0) + first if c.diff_attn else None

        def numbers(steps: int, stride: int):
            return None if start is None else (
                start + stride * jnp.arange(steps, dtype=jnp.int32))

        def in_line(h, part, kinds, layers, memory, number):
            per_layer = []
            for i, layer in enumerate(layers):
                h, aux_i, wrote = layer(
                    h, _take_layer(c, part, kinds, i), memory,
                    None if number is None else number + i)
                memory = {**memory, **wrote}
                per_layer.append(aux_i)
            return (h, jax.tree.map(lambda *a: jnp.stack(a), *per_layer),
                    memory)

        if not c.scan_layers or (any(writes) and n == period):
            # Unrolled: larger compile, but lets XLA schedule across layer
            # boundaries (and sidesteps scan-differentiation limits on some
            # backends when remat is off). A model that hands memory from
            # layer to layer and has no period runs so too.
            return in_line(x, stack, kinds, layers, memory, start)
        if any(roles[:n // period * period]):
            raise NotImplementedError(
                f"layers {first} to {first + n}: a layer that hands out a "
                f"memory inside a repeated period of {period} layers")
        if period == 1:
            x, auxs = _scan_layers(
                lambda h, ops: layers[0](h, ops[0], memory, ops[1])[:2], x,
                (stack, numbers(n, 1)), n)
            return x, auxs, memory
        # One scan step is one whole period, its layers unrolled: the
        # stacked [L, ...] weights are read as [L / P, P, ...] (a mixer's
        # own leaves as [L / P, layers of that kind a period, ...]); the
        # layers behind the last whole period run in line.
        whole, left = _split_stack(c, stack, kinds, period)
        x, auxs = _scan_layers(
            lambda h, ops: in_line(h, ops[0], kinds[:period], layers[:period],
                                   memory, ops[1])[:2],
            x, (whole, numbers(n // period, period)), n // period)
        if n % period:
            at = n // period * period
            x, more, memory = in_line(
                x, left, kinds[at:], layers[at:], memory,
                None if start is None else start + at)
            auxs = jax.tree.map(lambda a, b: jnp.concatenate(
                [a.reshape(-1, *a.shape[2:]), b]), auxs, more)
        return x, auxs, memory

    # ``layers`` holds what belongs to no one part of a block: the scan's
    # reads of the stacked weights and writes of their stacked gradients.
    with jax.named_scope("layers"):
        memory = {}
        if c.n_dense_layers:
            # the leading dense layers: a stack of their own, a scan (or
            # loop) of its own ahead of the expert layers'
            x, dense_auxs, memory = run_stack(
                x, params["dense_layers"], 0, c.n_dense_layers, memory,
                dense=True)
        x, auxs, _ = run_stack(x, params["layers"], c.n_dense_layers,
                               c.n_scan_layers, memory)
        stacks = [(c.n_dense_layers, c.n_scan_layers, auxs)]
        if c.n_dense_layers:
            stacks.append((0, c.n_dense_layers, dense_auxs))
        aux = dict(_fold(c, _counters(c), stacks), layers=auxs)

    with jax.named_scope("final_norm"):
        x = _norm(c, x, params["final_norm"])
    if return_hidden:
        return (x, aux) if return_aux else x
    with jax.named_scope("head_loss"):
        head = (params["embed"]["tokens"].T if c.tied else params["lm_head"])
        logits = jnp.einsum("btd,dv->btv", x, head.astype(dt),
                            preferred_element_type=jnp.float32)
        logits = con(logits, _BATCH, AXIS_SEQUENCE, AXIS_TENSOR)
    return (logits, aux) if return_aux else logits


def _norm(c: TransformerConfig, x, p):
    """The arch's norm of the stream ``x`` by the leaves ``p``."""
    if c.arch == "gpt2" or c.layer_norm:
        return layer_norm(x, p["w"], p["b"], eps=c.norm_eps)
    return rms_norm(x, _norm_weight(c, p["w"]), eps=c.norm_eps)


def _block(x, lp, c: TransformerConfig, *, rope, con, positions=None,
           kind=None, dense: bool = False, memory=None, writes: tuple = (),
           number=None, cut: bool = False):
    """One transformer block (pre-norm residual). Its parts carry the
    scopes ``attn_norm``, ``attn``, ``mlp_norm`` and ``mlp`` / ``moe``
    (see SCOPES); each part's residual add is inside its scope. ``kind``
    = (windowed, rope) is the layer's place in the ``layer_pattern``
    (static; None with no pattern: full causal attention, the arch's own
    positions), and names the sub-scope its attention runs under.
    ``kind`` = a name of ``mixers.MIXERS``: the token mixer is that one,
    under its record's scope. ``dense``: one of an expert model's leading
    dense layers. With
    ``post_norm`` a sublayer's output is normed under ``post_norm``,
    inside the sublayer's own scope, and joins the stream there. In a
    model of single-sublayer blocks (``single_sublayer``) the block is ONE
    of its halves: ``attn_norm`` + ``attn`` for a layer named by a mixer,
    ``mlp_norm`` + ``moe`` for one named "ffn". ``memory``: what earlier
    layers handed out, by name, for a mixer that reads one; ``writes``:
    the memories THIS layer hands out; ``number``: its published number;
    ``cut``: the stream between the two halves is a value of its own.
    Returns (x, every counter of the model, ``_counters``, by its ``key``:
    what this layer's sublayers report, zeros for what they do not, so
    that every layer of a stack reports alike, {a name in ``writes``: the
    memory})."""
    experts = c.n_experts > 0 and not dense

    def join(x, out, norm: str):
        """The residual stream with a sublayer's output added to it."""
        if not c.post_norm:
            return x + out
        with jax.named_scope(POST_NORM_SCOPE):
            return x + rms_norm(out, _norm_weight(c, lp[norm]["w"]),
                                eps=c.norm_eps)

    router, found, wrote = None, {}, {}
    if kind != FFN_ONLY:
        x, router, found, wrote = _mixer_sublayer(
            x, lp, c, rope=rope, con=con, positions=positions, kind=kind,
            experts=experts, join=join, memory=memory, writes=writes,
            number=number)
        if cut:
            # Found on the chip by bisection (PERF.md section 6, PR 62): an
            # attention layer IN LINE behind another layer gave a forward
            # alone one loss and a train step's forward another (up to 3e-4
            # at 16,384 tokens): XLA's two programs round what lies between
            # ``attn/wo``'s operand and the next norm at different cuts.
            # With a barrier here both read the SAME rounded stream.
            x = jax.lax.optimization_barrier(x)
    if kind == FFN_ONLY or not c.single_sublayer:
        x, more = _ffn_sublayer(x, lp, c, router=router, con=con,
                                experts=experts, join=join)
        found = {**found, **more}
    return x, {k.key: found[k.key] if k.key in found
               else jnp.zeros(k.shape(c), jnp.float32)
               for k in _counters(c)}, wrote


def _mixer_sublayer(x, lp, c: TransformerConfig, *, rope, con, positions,
                    kind, experts: bool, join, memory=None,
                    writes: tuple = (), number=None):
    """A block's first half: ``attn_norm`` and the token mixer under
    ``attn`` with its residual add -> (x, the router's logits where it
    reads this norm, else None, the mixer's counters by their keys, the
    memories named in ``writes``). The mixer is its record's ``apply``
    (``mixers.MIXERS``) on its own leaves; what it returns goes through
    ``attn/wo`` (and ``bo``), or, from a record with ``own_out``, joins the
    stream as it is."""
    dt = c.compute_dtype
    mixer = mixers.MIXERS[kind if isinstance(kind, str) else "attn"]
    window = None
    if isinstance(kind, tuple):         # attention's place in the pattern
        windowed, with_rope = kind
        window = c.sliding_window if windowed else None
        rope = rope if with_rope else None
    with jax.named_scope("attn_norm"):
        h = _norm(c, x, lp["ln1"])
    router = None
    if experts and c.router_input == "attn_norm":
        with jax.named_scope("moe"):
            router = moe.router_matmul(h, lp["router"]["w"])
    with jax.named_scope("attn"), (
            contextlib.nullcontext() if kind is None
            else jax.named_scope(mixer.scope(window))):
        o, counters, *wrote = mixer.apply(
            h, lp[mixer.stack(c)], c,
            mixers.Ctx(rope=rope, positions=positions, window=window,
                       con=con, memory=memory, layer=number))
        with jax.named_scope("attn_out"):
            if not mixer.own_out:
                wo = lp["attn"]["wo"].astype(dt)
                # one stack for every layer whose mixer is as wide inside
                # as attention: a linear layer's heads are its rows
                # regrouped (32 x 128 of Gated DeltaNet's for 16 x 256)
                if o.shape[2:] != wo.shape[:2]:
                    o = o.reshape(*o.shape[:2], *wo.shape[:2])
                o = jnp.einsum("bthk,hkd->btd", o, wo)
                if c.attn_bias:
                    o = o + lp["attn"]["bo"].astype(dt)
            if not c.post_norm:
                x = x + o
        if c.post_norm:
            x = join(x, o, "ln1_post")
    return x, router, counters, {name: wrote[0][name] for name in writes}


def _ffn_sublayer(x, lp, c: TransformerConfig, *, router, con,
                  experts: bool, join):
    """A block's second half: ``mlp_norm`` and the FFN under ``mlp`` /
    ``moe`` with its residual add -> (x, the router's and the shared
    expert's counters by their keys: none for a dense FFN)."""
    dt = c.compute_dtype
    counters = {}
    with jax.named_scope("mlp_norm"):
        h = _norm(c, x, lp["ln2"])
    if c.arch == "gpt2":
        with jax.named_scope("mlp"):
            m = gelu_mlp(h, lp["mlp"]["w_in"].astype(dt),
                         lp["mlp"]["b_in"].astype(dt),
                         lp["mlp"]["w_out"].astype(dt),
                         lp["mlp"]["b_out"].astype(dt))
            x = x + m
    elif experts:
        with jax.named_scope("moe"):
            m, counters = _expert_ffn(h, lp, c, router, con)
            x = join(x, m, "ln2_post")
    else:
        with jax.named_scope("mlp"):
            m = swiglu(h, lp["mlp"]["w_gate"].astype(dt),
                       lp["mlp"]["w_up"].astype(dt),
                       lp["mlp"]["w_down"].astype(dt))
            x = join(x, m, "ln2_post")
    return x, counters


# What an expert layer reports, each statistic named ONCE (``Counter``):
# the router's seven (``ops/moe.py`` hands them out under these keys; the
# counts are [expert layers, experts], NOT a scalar: the train step's rule
# for the router's bias reads them and takes them out of the metrics) and
# the mean of the shared expert's gate. The mixers' are their records'.
_moe = lambda c: c.n_experts > 0                            # noqa: E731
_held = lambda c: c.experts_held is not None                # noqa: E731
_biased = lambda c: c.router_bias                           # noqa: E731
_EXPERT_COUNTS = Counter("counts", "moe_expert_counts", "stack", _biased,
                         lambda c: (c.n_experts,))
_SHARED_GATE_MEAN = Counter("shared_gate_mean", "moe_shared_gate_mean",
                            "mean", lambda c: c.shared_expert_gate)
EXPERT_COUNTERS = (
    Counter("balance", "router_aux", "mean", _moe,
            weight="router_aux_weight"),
    Counter("z", "router_z", "mean", _moe, weight="router_z_weight"),
    Counter("load_max", "moe_load_max", "max", _moe),
    Counter("held_share", "moe_held_share", "mean", _held),
    Counter("full_buffer", "moe_full_buffer", "mean", _held),
    _EXPERT_COUNTS,
    Counter("bias_swapped", "moe_bias_swapped", "mean", _biased),
    _SHARED_GATE_MEAN,
)


def _counters(c: TransformerConfig) -> dict:
    """{a counter the model's layers report: the layers (of ``n_layers``)
    that report it}, in the order ``forward`` folds them: the expert
    layers', then the mixers' (``mixers.counters_of``)."""
    experts = tuple(i for i in c.layers_with(FFN_ONLY)
                    if i >= c.n_dense_layers)
    return {**{k: experts for k in EXPERT_COUNTERS if k.has(c)},
            **mixers.counters_of(c)}


# ``Counter.fold`` -> (a stack's rows to one number, two stacks' numbers to
# one). Every layer of a stack reports, zeros where it has no such
# sublayer, which none of these minds: a maximum >= 0, a minimum <= 0, a sum
_REDUCE = {"max": (jnp.max, jnp.maximum), "min": (jnp.min, jnp.minimum),
           "kind mean": (jnp.sum, jnp.add)}


def _fold(c: TransformerConfig, counters: dict, stacks: list) -> dict:
    """The step's statistics from every layer's: {a counter's ``metric``:
    its layers' values folded by its ``fold``}. ``stacks``: (the first
    layer, the number of layers, {a counter's ``key``: the layers' values
    as the scan stacked them}) of the main stack, then of the leading
    dense one; a stack none of whose layers reports a counter is left out
    of its fold. "mean" and "stack" are the expert layers' (one stack) and
    read the layers that report alone (a model of single-sublayer blocks:
    its FFN layers)."""
    index = functools.cache(jnp.asarray)    # one array a choice of rows
    out = {}
    for counter, layers in counters.items():
        parts = []      # (a stack's values, its rows that report or None)
        for first, n, aux in stacks:
            rows = tuple(i - first for i in layers if first <= i < first + n)
            if rows:
                parts.append((aux[counter.key], rows if len(rows) < n else ()))
        if counter.fold in _REDUCE:
            reduce, combine = _REDUCE[counter.fold]
            value = functools.reduce(combine, [reduce(a) for a, _ in parts])
            if counter.fold == "kind mean":
                value = value / len(layers)
        else:
            (value, rows), = parts
            if rows or counter.fold == "stack":
                value = value.reshape(-1, *counter.shape(c))
            if rows:
                value = value[index(rows)]
            if counter.fold == "mean":
                value = value.mean()
        out[counter.metric] = value
    return out


def _expert_ffn(h, lp, c: TransformerConfig, router, con):
    """An expert layer's FFN on its normed input ``h`` [B, T, D] -> (the
    held experts' part of the routed sum plus the shared expert, the
    layer's counters by their keys), AHEAD of any output norm and of the
    residual add. ``router``: the logits, where they were made ahead of
    attention."""
    dt = c.compute_dtype
    # experts with no gate projection have no such leaf (``expert_gated``)
    weights = (lp["router"]["w"], lp["mlp"].get("w_gate"),
               lp["mlp"]["w_up"], lp["mlp"]["w_down"])
    if c.expert_capacity_factor is not None:
        return moe.moe_swiglu(
            h, *weights, top_k=c.expert_top_k,
            capacity_factor=c.expert_capacity_factor,
            norm_topk=c.expert_norm_topk,
            # Group count n can be 1 (< data-axis size), so only the
            # expert dim is constrained; GSPMD lays out the rest.
            constrain_fn=lambda t: con(t, None, AXIS_EXPERT, None, None))
    m, aux = moe.moe_swiglu_dropless(
        h, *weights, top_k=c.expert_top_k, norm_topk=c.expert_norm_topk,
        router_logits=router, held=c.held_range,
        activation=c.expert_activation, score=c.router_score,
        select_bias=lp["router"].get("b"), gate_scale=c.expert_gate_scale)
    if c.d_ff_shared:
        if not c.expert_gated:
            m = m + moe.shared_expert(
                h, None, lp["mlp"]["shared_w_up"].astype(dt),
                lp["mlp"]["shared_w_down"].astype(dt), c.expert_activation)
            return m, aux
        shared = [lp["mlp"][f"shared_{name}"].astype(dt)
                  for name in ("w_gate", "w_up", "w_down")]
        if c.shared_expert_gate:
            out, gate_mean = moe.gated_shared_expert(
                h, *shared, lp["mlp"]["shared_gate"])
            m, aux = m + out, {**aux, _SHARED_GATE_MEAN.key: gate_mean}
        else:
            m = m + moe.shared_expert(h, *shared)
    return m, aux


# -- loss / train step ------------------------------------------------------

def cross_entropy_loss(logits, targets, *, mask=None, z_loss: float = 0.0):
    """Token-level CE in float32 with optional z-loss regularizer.

    logits [B,T,V] (any dtype; upcast), targets [B,T] int, mask [B,T]
    (1 = contributes). Returns (scalar loss, dict metrics).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is None:
        denom = nll.size
        loss = nll.sum() / denom
        acc = (logits.argmax(-1) == targets).mean()
    else:
        mask = mask.astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
        acc = ((logits.argmax(-1) == targets) * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": acc,
                  "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def chunked_ce_loss(x, head, targets, *, mask=None, z_loss: float = 0.0,
                    chunk: int = 2048, accuracy: bool = True):
    """CE over a chunked LM head: x [B,T,D] (final hidden), head [D,V].

    Logits exist only chunk-at-a-time inside a remat'd lax.scan — the
    backward pass recomputes each chunk's logits instead of keeping the
    [B,T,V] float32 tensor alive, trading ~1 extra head matmul for
    gigabytes of HBM (what actually caps batch size on one chip)."""
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    tf = targets.reshape(N)
    mf = (mask.reshape(N).astype(jnp.float32) if mask is not None
          else jnp.ones((N,), jnp.float32))
    chunk = min(chunk, N)
    n_chunks = (N + chunk - 1) // chunk
    pad = n_chunks * chunk - N
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, D), xf.dtype)])
        tf = jnp.concatenate([tf, jnp.zeros((pad,), tf.dtype)])
        mf = jnp.concatenate([mf, jnp.zeros((pad,), mf.dtype)])
    xc = xf.reshape(n_chunks, chunk, D)
    tc = tf.reshape(n_chunks, chunk)
    mc = mf.reshape(n_chunks, chunk)

    @jax.checkpoint
    def body(carry, xs):
        nll_sum, correct_sum = carry
        xb, tb, mb = xs
        logits = jnp.einsum("cd,dv->cv", xb, head,
                            preferred_element_type=jnp.float32)
        nll_s, corr_s, _ = _ce_chunk_stats(logits, tb, mb, z_loss, accuracy)
        return (nll_sum + nll_s, correct_sum + corr_s), None

    (nll_sum, correct_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc, mc),
    )
    denom = jnp.maximum(mf.sum(), 1.0)
    loss = nll_sum / denom
    acc = correct_sum / denom
    return loss, {"loss": loss, "accuracy": acc,
                  "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def _ce_chunk_stats(logits, tb, mb, z_loss, accuracy):
    """Shared per-block CE statistics: (nll_masked_sum, correct_masked_sum,
    lse). logits fp32 [..., V]; tb [...] int; mb [...] fp32."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    correct = ((logits.argmax(-1) == tb).astype(jnp.float32) * mb).sum() \
        if accuracy else jnp.zeros((), jnp.float32)
    return (nll * mb).sum(), correct, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_chunked_ce_loss(x, head, targets, mask, z_loss, chunk, accuracy,
                          con=None):
    """LM head + CE as one op whose BACKWARD is computed analytically in
    the forward (dlogits = softmax - onehot), so the logits' matmul runs
    exactly once per train step — vs jax.checkpoint's
    recompute-in-backward (see TransformerConfig.ce_impl) — and no float32
    logits live from the forward to the backward. x [..., D] (final
    hidden), head [D,V], targets [...] int, mask [...] f32. With
    ``chunk`` rows or fewer it is ONE block on the operands as they are
    (``con``, if given, constrains the block's logits' sharding); with
    more, a scan over blocks of ``chunk`` of the flattened rows. Returns
    (loss, acc). The un-differentiated call (eval) skips the gradient
    work entirely."""
    nll_sum, correct_sum, denom = _fused_ce_scan(
        x, head, targets, mask, z_loss, chunk, accuracy, con,
        want_grads=False)
    return nll_sum / denom, correct_sum / denom


def _fused_ce_block(xb, head, tb, mb, denom, z_loss, accuracy, con,
                    want_grads):
    """One block of rows: (nll_sum, correct_sum) and, with want_grads,
    (dx [..., D] f32, dhead [D,V] f32)."""
    logits = jnp.einsum("...d,dv->...v", xb, head,
                        preferred_element_type=jnp.float32)
    if con is not None:
        logits = con(logits)
    nll_s, corr_s, lse = _ce_chunk_stats(logits, tb, mb, z_loss, accuracy)
    if not want_grads:
        return (nll_s, corr_s), None
    # dloss/dlogits for loss = sum(nll*m)/denom:
    #   (softmax * (1 + 2*z*lse) - onehot) * m / denom
    p = jnp.exp(logits - lse[..., None])
    dl = p * (1.0 + 2.0 * z_loss * lse)[..., None] if z_loss else p
    # onehot subtraction as an iota-compare (TPU scatter is slow, and
    # jax's transposed take_along_axis scatters into a linear [N * V])
    onehot = (jax.lax.broadcasted_iota(jnp.int32, dl.shape, dl.ndim - 1)
              == tb[..., None])
    dl = (dl - onehot.astype(dl.dtype)) * (mb / denom)[..., None]
    # bf16 matmul operands (MXU), fp32 accumulation: same precision
    # story as the rest of the model's backward.
    dlc = dl.astype(head.dtype)
    dxb = jnp.einsum("...v,dv->...d", dlc, head,
                     preferred_element_type=jnp.float32)
    dhead = jnp.einsum("...d,...v->dv", xb.astype(head.dtype), dlc,
                       preferred_element_type=jnp.float32)
    return (nll_s, corr_s), (dxb, dhead)


def _fused_ce_scan(x, head, targets, mask, z_loss, chunk, accuracy, con,
                   want_grads):
    """Returns (nll_sum, correct_sum, denom) and, with want_grads, also
    (dx like x, f32-accurate, dhead [D,V] f32): the cotangents of x/head
    for a unit loss cotangent, already including the 1/denom and z_loss
    terms."""
    D, V = head.shape
    N = targets.size
    denom = jnp.maximum(mask.sum(), 1.0)
    if chunk >= N:
        (nll_sum, correct_sum), grads = _fused_ce_block(
            x, head, targets, mask, denom, z_loss, accuracy, con, want_grads)
        sums = (nll_sum, correct_sum, denom)
        return (sums, grads) if want_grads else sums
    n_chunks = (N + chunk - 1) // chunk
    pad = n_chunks * chunk - N
    xf, tf, mf = x.reshape(N, D), targets.reshape(N), mask.reshape(N)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, D), xf.dtype)])
        tf = jnp.concatenate([tf, jnp.zeros((pad,), tf.dtype)])
        mf = jnp.concatenate([mf, jnp.zeros((pad,), mf.dtype)])
    xs = (xf.reshape(n_chunks, chunk, D), tf.reshape(n_chunks, chunk),
          mf.reshape(n_chunks, chunk))

    def body(carry, block):
        xb, tb, mb = block
        (nll_s, corr_s), grads = _fused_ce_block(
            xb, head, tb, mb, denom, z_loss, accuracy, None, want_grads)
        if not want_grads:
            return (carry[0] + nll_s, carry[1] + corr_s), None
        dxb, dhead = grads
        return (carry[0] + nll_s, carry[1] + corr_s, carry[2] + dhead), dxb

    zero = jnp.zeros((), jnp.float32)
    if not want_grads:
        (nll_sum, correct_sum), _ = jax.lax.scan(body, (zero, zero), xs)
        return nll_sum, correct_sum, denom
    (nll_sum, correct_sum, dhead), dxc = jax.lax.scan(
        body, (zero, zero, jnp.zeros((D, V), jnp.float32)), xs)
    dx = dxc.reshape(n_chunks * chunk, D)[:N].reshape(x.shape)
    return (nll_sum, correct_sum, denom), (dx, dhead)


def _fused_ce_fwd(x, head, targets, mask, z_loss, chunk, accuracy, con):
    (nll_sum, correct_sum, denom), (dx, dhead) = _fused_ce_scan(
        x, head, targets, mask, z_loss, chunk, accuracy, con,
        want_grads=True)
    return ((nll_sum / denom, correct_sum / denom),
            (dx.astype(x.dtype), dhead.astype(head.dtype)))


def _fused_ce_bwd(z_loss, chunk, accuracy, con, res, g):
    import numpy as np

    dx, dhead = res
    g_loss, _g_acc = g  # accuracy is a metric; its cotangent is dropped
    rows = dx.shape[:-1]
    # targets are int (float0 cotangent); mask is standardized to f32 by
    # the callers (lm_loss) so its zero cotangent dtype is static here.
    return ((dx * g_loss).astype(dx.dtype),
            (dhead * g_loss).astype(dhead.dtype),
            np.zeros(rows, jax.dtypes.float0),
            jnp.zeros(rows, jnp.float32))


fused_chunked_ce_loss.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def lm_loss(params, batch, config: TransformerConfig, *, mesh=None,
            z_loss: float = 0.0):
    """Next-token LM loss. batch: {"tokens": [B,T]} (targets = shift) or
    {"inputs","targets"[,"mask"]}. The head and the cross entropy are ONE
    op on the final hidden state (``fused_chunked_ce_loss``: no float32
    [B,T,V] lives from the forward to the backward; ``cross_entropy_loss``
    on ``forward``'s logits is the same mathematics, and the tests'
    oracle). At ``config.loss_chunk`` 0 it is ONE block of all the rows;
    a caller's ``loss_chunk`` and ``ce_impl`` mean what they meant. The
    op has a gradient rule of its own, so at every ``loss_chunk`` the
    loss differentiates in reverse mode only (``jax.jvp`` and
    ``jax.hessian`` of it fail), and ``config.ce_accuracy`` False zeroes
    ``accuracy`` at 0 as it does in blocks."""
    if "inputs" in batch:
        inp, tgt = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    else:
        toks = batch["tokens"]
        inp, tgt = toks[:, :-1], toks[:, 1:]
        mask = batch.get("mask")
        if mask is not None:
            mask = mask[:, 1:]
    if config.loss_chunk > 0 and config.ce_impl not in ("fused",
                                                         "checkpoint"):
        raise ValueError(
            f"ce_impl must be 'fused' or 'checkpoint', got "
            f"{config.ce_impl!r}")
    x, aux = forward(params, inp, config, mesh=mesh, return_aux=True,
                     return_hidden=True)
    with jax.named_scope("head_loss"):
        head = (params["embed"]["tokens"].T if config.tied
                else params["lm_head"]).astype(config.compute_dtype)
        if config.loss_chunk > 0 and config.ce_impl == "checkpoint":
            loss, metrics = chunked_ce_loss(x, head, tgt, mask=mask,
                                            z_loss=z_loss,
                                            chunk=config.loss_chunk,
                                            accuracy=config.ce_accuracy)
        else:
            chunk, con = config.loss_chunk, None
            if not chunk:
                # ONE block of all the rows, which under a mesh stay on
                # their devices: the logits are written once and read by
                # the sum, the argmax and both backward matmuls, which
                # make ``dlogits`` in their operand fusions (PERF.md, PR
                # 52). Logits that do not fit want a ``loss_chunk``: no
                # cell has such (2.49 GB at most), so no rule picks one.
                chunk = tgt.size
                if mesh is not None:
                    def con(logits):
                        return constrain(logits, mesh, _BATCH, AXIS_SEQUENCE,
                                         AXIS_TENSOR)
            mf = (jnp.ones(tgt.shape, jnp.float32) if mask is None
                  else mask.astype(jnp.float32))
            loss, acc = fused_chunked_ce_loss(
                x, head, tgt, mf, float(z_loss), int(chunk),
                bool(config.ce_accuracy), con)
            metrics = {"loss": loss, "accuracy": acc,
                       "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}
    # every counter the model's layers report, under its name in the
    # table that names it (``_counters``); a loss term times its weight
    counters = _counters(config)
    for counter in counters:
        if counter.weight is not None:
            loss = loss + (getattr(config, counter.weight)
                           * aux[counter.metric])
    metrics = dict(metrics, loss=loss,
                   **{k.metric: aux[k.metric] for k in counters})
    return loss, metrics


def make_train_step(config: TransformerConfig, optimizer, *, mesh=None,
                    z_loss: float = 0.0, accum_steps: int = 1):
    """Build the jittable training step.

    state: {"params", "opt_state", "step"}. With a mesh, jit it with
    donate_argnums=(0,) and sharded in/out shardings (see
    parallel.sharding.shard_params); GSPMD inserts the grad
    reduce-scatters/all-reduces the reference gets from DDP/FSDP wrappers
    (reference: train/torch/train_loop_utils.py:12,36).

    ``accum_steps > 1`` enables gradient accumulation: every batch leaf's
    leading dim must be a multiple of accum_steps; the step scans over
    accum_steps microbatches, accumulates grads in fp32 weighted by each
    microbatch's valid-token count (so masked batches match the
    unaccumulated step's per-token weighting), and applies the optimizer
    ONCE — the activation-memory footprint of a 1/accum batch at the
    effective batch size of the whole one. Every metric lm_loss reports
    (incl. router_aux, router_z and moe_load_max for MoE) is the same
    weighted average; perplexity is the weighted mean of per-microbatch
    perplexities (exp is convex, so it can sit slightly above the
    unaccumulated exp-of-mean value).

    A model with a ``router_bias`` gets one update outside the
    optimizer's: after it, every layer's bias is set to what it was
    BEFORE the optimizer plus ``router_bias_rate x sign(mean load - the
    expert's load)``, from the step's own assignment counts over all the
    experts (DeepSeek-V3's rule, arXiv:2412.19437), so neither the
    optimizer's update nor its weight decay ever moves it (its gradient
    is exactly zero). ``router_bias_absmax`` in the metrics says how far
    the rule has taken it.
    """

    def loss_fn(params, batch):
        return lm_loss(params, batch, config, mesh=mesh, z_loss=z_loss)

    from ray_tpu.ops.optim import FusedClipAdamW

    fused = isinstance(optimizer, FusedClipAdamW)

    def grads_of(params, batch):
        if accum_steps <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

        def to_micro(x):
            n = x.shape[0]
            if n % accum_steps:
                raise ValueError(
                    f"batch dim {n} not divisible by accum_steps "
                    f"{accum_steps}")
            return x.reshape(accum_steps, n // accum_steps, *x.shape[1:])

        micro = jax.tree.map(to_micro, batch)

        def micro_weight(mb):
            # Valid-TARGET-token count: lm_loss means over this, so
            # weighting by it reproduces the full-batch per-token mean.
            mask = mb.get("mask")
            if mask is not None:
                m = mask[:, 1:] if "tokens" in mb else mask
                return m.astype(jnp.float32).sum()
            toks = mb["tokens"] if "tokens" in mb else mb["targets"]
            n_t = toks.shape[0] * (toks.shape[1] - (1 if "tokens" in mb
                                                    else 0))
            return jnp.float32(n_t)

        # Metric structure is config-static: one abstract eval gives the
        # zero carry for ANY key set lm_loss reports (router_aux, ...).
        first = jax.tree.map(lambda x: x[0], micro)
        m_shape = jax.eval_shape(loss_fn, params, first)[1]
        mzero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m_shape)
        gzero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def scan_body(carry, mb):
            gsum, msum, wsum = carry
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            with jax.named_scope("grad_accum"):
                w = micro_weight(mb)
                gsum = jax.tree.map(
                    lambda a, g: a + w * g.astype(jnp.float32), gsum, grads)
                msum = jax.tree.map(lambda a, m: a + w * m, msum, metrics)
                return (gsum, msum, wsum + w), None

        (gsum, msum, wsum), _ = jax.lax.scan(
            scan_body, (gzero, mzero, jnp.zeros((), jnp.float32)), micro)
        with jax.named_scope("grad_accum"):
            inv = 1.0 / jnp.maximum(wsum, 1.0)
            grads = jax.tree.map(lambda g: g * inv, gsum)
            metrics = jax.tree.map(lambda m: m * inv, msum)
        return (metrics["loss"], metrics), grads

    def train_step(state, batch):
        (loss, metrics), grads = grads_of(state["params"], batch)
        with jax.named_scope("optimizer"):
            if fused:
                # Single fused pass: clip + AdamW + param update in one
                # kernel per leaf, grad norm shared with the metric (the
                # optax path below reads the grads three times for the
                # same result — ~35 ms/step on GPT-2 124M @ v5e).
                params, opt_state, gnorm = optimizer.apply(
                    grads, state["opt_state"], state["params"]
                )
            else:
                updates, opt_state = optimizer.update(
                    grads, state["opt_state"], state["params"]
                )
                params = jax.tree.map(
                    lambda p, u: (p + u.astype(p.dtype)), state["params"],
                    updates
                )
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)
                ))
        metrics = dict(metrics, grad_norm=gnorm)
        if config.router_bias:
            counts = metrics.pop(_EXPERT_COUNTS.metric)
            with jax.named_scope("optimizer"):
                b = state["params"]["layers"]["router"]["b"]
                b = b + config.router_bias_rate * jnp.sign(
                    counts.mean(-1, keepdims=True) - counts).astype(b.dtype)
            router = dict(params["layers"]["router"], b=b)
            params = dict(params, layers=dict(params["layers"],
                                              router=router))
            metrics["router_bias_absmax"] = jnp.abs(b).max()
        with set_xla_metadata(scopes=SCOPES_ID):     # into the cache key
            count = state["step"] + 1
        return {"params": params, "opt_state": opt_state,
                "step": count}, metrics

    return train_step


def init_train_state(rng, config: TransformerConfig, optimizer):
    params = init_params(rng, config)
    return {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


# -- decode (KV cache) ------------------------------------------------------

def refuse_decode(c: TransformerConfig) -> None:
    """The KV-cache decode runs one kind of dense layer: refuse, by name,
    a model it would run wrongly in silence."""
    # a model's mixers say why themselves (their records' ``decodes``); a
    # layer with NO mixer in the words of the mixer that stands beside such
    # layers, attention under ``layer_mixers`` (its own leaves in a stack of
    # their own) in the linear mixers'
    if c.layer_mixers:
        why = mixers.refuses_single_sublayers if c.single_sublayer else next(
            (mixer.decodes for kind, mixer in mixers.MIXERS.items()
             if kind in c.layer_mixers and mixer.decodes),
            mixers.refuses_linear)
        raise NotImplementedError(why(c))
    for name, value in (("kv_latent", c.kv_latent),
                        ("latent_rope", not c.latent_rope),
                        ("n_dense_layers", c.n_dense_layers),
                        ("d_ff_shared", c.d_ff_shared),
                        ("qk_norm", c.qk_norm),
                        ("attn_gate", c.attn_gate),
                        ("post_norm", c.post_norm),
                        ("rope_fraction", c.rope_fraction != 1.0
                         and c.rope_fraction),
                        ("norm_zero_centred", c.norm_zero_centred),
                        ("shared_expert_gate", c.shared_expert_gate),
                        ("embed_scale", c.embed_scale != 1.0
                         and c.embed_scale),
                        ("diff_attn", c.diff_attn),
                        ("attn_bias", c.attn_bias),
                        ("layer_norm", c.layer_norm)):
        if value:
            raise NotImplementedError(
                f"KV-cache decode does not run a model with {name} "
                f"({value!r}): the cache holds kv_heads x head_dim x 2 a "
                f"token and every layer would be decoded as a dense one "
                f"of plain attention, its q and k not normed, its heads "
                f"rotated whole, its norms RMS and not zero-centred, its "
                f"output neither gated nor normed, its projections without "
                f"a bias, its embedding not scaled")
    if c.n_experts > 0:
        raise NotImplementedError(
            "KV-cache decode for MoE models is not implemented yet"
        )
    for name, value in (("layer_pattern", c.layer_pattern),
                        ("sliding_window", c.sliding_window),
                        ("experts_held", c.experts_held)):
        if value:
            raise NotImplementedError(
                f"KV-cache decode does not run a model with {name} "
                f"({value!r}): every layer would be decoded as full causal "
                f"attention with RoPE")


def init_kv_cache(config: TransformerConfig, batch_size: int, max_len: int):
    """Preallocated decode cache: [L, B, max_len, KV, Dh] per k/v."""
    c = config
    refuse_decode(c)
    shape = (c.n_layers, batch_size, max_len, c.kv_heads, c.head_dim)
    return {
        "k": jnp.zeros(shape, c.compute_dtype),
        "v": jnp.zeros(shape, c.compute_dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(params, tokens, cache, config: TransformerConfig):
    """One autoregressive step: tokens [B, S] appended at cache['pos'].

    Returns (logits [B, S, V] float32, updated cache). S=1 for pure
    decode; S>1 for prefill. Static shapes throughout → one compiled
    program serves both prefill (S=prompt) and decode (S=1).
    """
    c = config
    refuse_decode(c)
    dt = c.compute_dtype
    B, S = tokens.shape
    pos0 = cache["pos"]
    positions = pos0 + jnp.arange(S)

    x = params["embed"]["tokens"][tokens].astype(dt)
    if c.arch == "gpt2":
        x = x + params["embed"]["pos"][positions].astype(dt)
        rope = None
    else:
        cos, sin = rope_frequencies(c.head_dim, c.max_seq_len,
                                    theta=c.rope_theta)
        rope = (cos, sin)

    def layer(x, lp_and_cache):
        lp, kc, vc = lp_and_cache
        if c.arch == "gpt2":
            h = layer_norm(x, lp["ln1"]["w"], lp["ln1"]["b"])
        else:
            h = rms_norm(x, lp["ln1"]["w"])
        q = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wq"].astype(dt))
        k = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wk"].astype(dt))
        v = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wv"].astype(dt))
        if rope is not None:
            q = apply_rope(q, *rope, positions=positions)
            k = apply_rope(k, *rope, positions=positions)
        kc = jax.lax.dynamic_update_slice(kc, k, (0, pos0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, pos0, 0, 0))
        kf, vf = _expand_gqa(kc, vc, c)
        # Causality against global positions doubles as the cache-validity
        # mask: unwritten slots sit at k_pos > current positions.
        o = dot_product_attention(q, kf, vf, causal=True,
                                  q_offset=pos0).astype(dt)
        o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"].astype(dt))
        x = x + o
        if c.arch == "gpt2":
            h = layer_norm(x, lp["ln2"]["w"], lp["ln2"]["b"])
            m = gelu_mlp(h, lp["mlp"]["w_in"].astype(dt),
                         lp["mlp"]["b_in"].astype(dt),
                         lp["mlp"]["w_out"].astype(dt),
                         lp["mlp"]["b_out"].astype(dt))
        else:
            h = rms_norm(x, lp["ln2"]["w"])
            m = swiglu(h, lp["mlp"]["w_gate"].astype(dt),
                       lp["mlp"]["w_up"].astype(dt),
                       lp["mlp"]["w_down"].astype(dt))
        return x + m, (kc, vc)

    def scan_body(x, xs):
        lp, kc, vc = xs
        x, (kc, vc) = layer(x, (lp, kc, vc))
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        scan_body, x, (params["layers"], cache["k"], cache["v"])
    )
    if c.arch == "gpt2":
        x = layer_norm(x, params["final_norm"]["w"], params["final_norm"]["b"])
    else:
        x = rms_norm(x, params["final_norm"]["w"])
    head = (params["embed"]["tokens"].T if c.tied else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt),
                        preferred_element_type=jnp.float32)
    new_cache = {"k": k_new, "v": v_new, "pos": pos0 + S}
    return logits, new_cache


def generate(params, prompt, config: TransformerConfig, *, max_new_tokens: int,
             temperature: float = 0.0, rng=None, max_len: int | None = None):
    """Greedy/temperature sampling loop (prefill + lax.scan decode)."""
    # Accept numpy param trees (e.g. fresh from device_get / a checkpoint):
    # numpy arrays can't be indexed by tracers inside the scan.
    params = jax.tree.map(jnp.asarray, params)
    prompt = jnp.asarray(prompt)
    B, T = prompt.shape
    max_len = min(max_len or T + max_new_tokens, config.max_seq_len)
    # Never decode past the cache/pos-embedding capacity: out-of-range
    # dynamic_update_slice writes clamp silently and corrupt the cache.
    max_new_tokens = min(max_new_tokens, max_len - T)
    if max_new_tokens <= 0:
        return prompt
    cache = init_kv_cache(config, B, max_len)
    logits, cache = decode_step(params, prompt, cache, config)
    last = logits[:, -1]
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(key, lg):
        if temperature == 0.0:
            return lg.argmax(-1).astype(prompt.dtype)
        return jax.random.categorical(key, lg / temperature).astype(prompt.dtype)

    def step(carry, key):
        cache, lg = carry
        tok = sample(key, lg)
        logits, cache = decode_step(params, tok[:, None], cache, config)
        return (cache, logits[:, -1]), tok

    keys = jax.random.split(rng, max_new_tokens)
    (_, _), toks = jax.lax.scan(step, (cache, last), keys)
    return jnp.concatenate([prompt, toks.T], axis=1)
