"""Sequence torsos: a language model's blocks as the shared trunk of actor
and critic.

A torso reads a flat float32 observation (a stacked history, as the pixel
rows stack frames), turns every value into a token, runs the tokens through
transformer layers and pools them into one latent per row; D4PG's own heads
(``models/actor.py``, ``models/critic.py``) read the latent as they read a
state vector. ``D4PGConfig.torso`` names one (``TORSOS``) with its sizes
(``TorsoSpec``, made from a configuration file's ``model.torso`` block).

Seven models share the one layer path, told apart by the data in the spec
(``layer_types`` or ``hybrid_override_pattern``, ``qk_norm``, ``sa_config``,
``num_dense_layers``, ``router_scores``, ``use_expert_bias``,
``attn_output_gate``, ``partial_rotary_factor``, ``rope_parameters``,
``shared_expert_intermediate_size``, ``shared_expert_gated``,
``mlp_hidden_act``, the ``linear_*`` and Mamba sizes, ``num_experts`` 0,
``sandwich_norm``, ``total_ut_steps``, ``embedding_multiplier``), not by code
of their own. A layer of
two branches is ``x + Op(RMSNorm(x))`` then ``x + FF(RMSNorm(x))``, with
``sandwich_norm`` ``x + RMSNorm(Op(RMSNorm(x)))`` then ``x +
RMSNorm(FF(RMSNorm(x)))``; a block of one branch (``PATTERN``: ``mamba``,
``attention``, ``moe``) is ``x + Op(RMSNorm(x))`` OR ``x +
FF(RMSNorm(x))`` alone:

- ``mellum2``, the Mellum2-12B-A2.5B layer: RMSNorm, grouped-query
  attention with rotary embeddings (default on ``sliding_attention``
  layers, YaRN on ``full_attention`` ones) under a static mask
  (``ops/attention.py``).
- ``keye2``, the Keye-VL-2.0-30B-A3B language-model layer
  (``sparse_attention``): the same grouped-query attention with RMSNorm on
  every head of ``q`` and ``k``, over keys an indexer chooses at run time
  (``ops/sparse_attention.py``): ``indexer_num_heads`` small heads score
  every earlier position on the layer's normed input through a
  stop-gradient, the ``topk`` best are read. The selection carries no
  gradient; the indexer's own parameters (``index_q``, ``index_k``,
  ``index_k_norm``, ``index_w``) are trained by an alignment loss that
  ``apply(..., train=True)`` hands up beside the counters (``index_loss``,
  ``select_counts``) and that reaches nothing else; its target is read
  off the main attention's own forward call (that call's log-sum-exp),
  not a second one.
- ``lfm2``, LFM2-8B-A1B's layers: ``conv`` layers whose operator is a gated
  short convolution with no attention at all (``ops/short_conv.py``: ``[b,
  c, u] = h W_in``, a depthwise causal convolution of ``conv_L_cache`` taps
  over ``b * u``, ``out_proj`` of ``c`` times it) round ``full_attention``
  layers of 64-wide heads with q/k norm; the first ``num_dense_layers``
  layers' feed-forward is a dense SwiGLU of ``intermediate_size``, the
  others' the expert layer under a ``sigmoid`` router with a
  load-balancing bias.
- ``qwen3next``, Qwen3-Next-80B-A3B's layers: ``linear_attention`` layers
  whose operator is a Gated DeltaNet (``_delta``: ``[q, k, v, z] = h
  W_qkvz``, ``[b, a] = h W_ba``, a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps and a SiLU over ``q, k, v``, l2-normed
  ``q`` and ``k`` a head, the gated delta rule of ``ops/delta_rule.py``
  with write strength ``sigmoid(b)`` and log-decay ``-exp(A_log) softplus(a
  + dt_bias)`` a value head, an RMSNorm a head gated by ``silu(z)``,
  ``out_proj``; a state carried along the sequence, no rotary embedding)
  round ``full_attention`` layers of 256-wide heads whose ``q`` projection
  is twice as wide (``attn_output_gate``: a head's query, then the logits
  of a sigmoid gate on its output) and whose rotary embedding turns the
  first ``partial_rotary_factor`` of a head; every layer's feed-forward is
  the expert layer with a shared expert added whole under a scalar sigmoid
  gate (``shared_expert_intermediate_size``). ``aux["delta_kept"]`` is the
  mean of ``exp(g)`` a DeltaNet layer, ``aux["shared_gate"]`` the mean of
  the shared expert's gate a layer.
- ``ouro``, Ouro-2.6B's looped (depth-recurrent) dense stack: ungrouped
  ``full_attention`` layers (as many key/value heads as query heads) with a
  dense SwiGLU in EVERY layer (``num_experts`` 0, ``num_dense_layers`` all of
  them, ``experts_held`` ``[0, 0]``: no router, no ``route_counts``,
  ``balance`` a no-op, ``grouped_impl`` never asked) and ``sandwich_norm``: a
  second RMSNorm with its own gain behind the operator and behind the
  feed-forward. ``total_ut_steps`` R > 1 runs the whole stack R times on its
  own output with the SAME leaves (``_passes``): ``x_t =
  final_norm(Layers(x_{t-1}))``, the one final norm behind every pass, its
  output what the next pass starts from and what is pooled (``u_t``, pass
  ``t``'s latent). ``apply`` returns ``u_R`` (``early_exit_threshold`` 1: no
  pass is ever skipped; another threshold is refused); with ``train=True``
  ``aux`` holds every pass's latent (``pass_latents [R, B, D]``) and the exit
  gate's logits (``exit_logits [R, B]``, ``exit_gate``: a ``[D, 1]`` kernel
  and a bias on the pooled latent, float32), from which
  ``exit_distribution`` makes a row's distribution over the passes and
  ``learner/update._expected_exit_loss`` the critic loss (the expectation of
  the TD loss over it less ``exit_entropy_beta`` times its entropy). A looped
  torso's layers are attention or conv with a dense feed-forward: it hands
  up no counters.
- ``nemotronh``, Nemotron-H's blocks (the language tower of
  Nemotron-Labs-TwoTower-30B-A3B), **each one branch**, a character a block
  in ``hybrid_override_pattern`` (the published key; ``pattern_blocks``):
  ``M`` a Mamba-2 mixer (``_mamba``: ``[z, xBC, dt] = h W_in``, a depthwise
  causal convolution of ``conv_kernel`` taps WITH a bias and a SiLU over
  ``xBC``, which splits into ``mamba_num_heads`` heads of ``mamba_head_dim``
  and ``B``, ``C`` in ``n_groups`` groups of ``ssm_state_size``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space recurrence
  of ``ops/ssd.py`` in chunks of ``chunk_size`` with the skip ``D``; the
  output gated by ``silu(z)`` BEFORE an RMSNorm over each group of ``inner /
  n_groups`` channels; ``out_proj``), ``*`` grouped-query attention with no
  rotary embedding (no block for it in ``rope_parameters``: the Mamba blocks
  carry position), ``E`` the expert layer under the ``sigmoid`` router with
  its bias and ``routed_scaling_factor``, its experts ``down(relu(up h) **
  2)`` (``mlp_hidden_act`` ``relu2``: two matrices, no ``gate`` leaf) and a
  shared expert of the same form added whole and ungated
  (``shared_expert_gated`` false). ``aux["ssd_kept"]`` is the mean of
  ``exp(dt A)`` a Mamba block; ``route_counts`` and ``bias_swapped`` have a
  row an ``E`` block.
- ``trinity``, Trinity-Mini's layers (``afmoe``), every mechanism one of the
  flags above, here together: the embedding times ``embedding_multiplier``
  (``sqrt(hidden_size)``); ``sandwich_norm`` round BOTH branches of every
  layer, the leading dense one (``num_dense_layers``) and the expert layers
  alike, the feed-forward's post-norm on routed + shared as one sum;
  ``sliding_attention`` layers with rotary embedding round ``full_attention``
  layers with NONE (``rope_parameters`` names the type with an explicit
  ``null``: a type left out stays an error), both with ``qk_norm`` before the
  rotation and ``attn_output_gate`` (a head's query then its gate in ``q``'s
  columns: afmoe's separate ``gate_proj`` with the columns of ``[Wq | Wg]``
  in another order); the ``sigmoid`` router with its bias and
  ``routed_scaling_factor``, gated-SiLU experts and an ungated shared expert
  of the same form.

Leaves. A layer has only the leaves its kind has: the operator's are
``attn_norm``, ``q``, ``k``, ``v``, ``o`` (with ``qk_norm`` also ``q_norm``,
``k_norm``; a sparse layer's indexer beside them) or ``conv_norm``,
``in_proj``, ``conv`` (the taps, ``[D, conv_L_cache]``), ``out_proj`` or
``linear_norm``, ``in_proj_qkvz``, ``in_proj_ba``, ``conv`` (``[2 Wk + Wv,
linear_conv_kernel_dim]``), ``A_log``, ``dt_bias``, ``out_norm``,
``out_proj`` (with ``attn_output_gate`` an attention layer's ``q`` is ``[D,
2 H Dh]``) or ``mamba_norm``, ``in_proj`` (``[D, 2 inner + 2 G N + H]``),
``conv`` (taps ``[inner + 2 G N, conv_kernel]`` and a ``bias``), ``A_log``,
``dt_bias``, ``D``, ``out_norm`` (``[inner]``), ``out_proj``; the
feed-forward's ``mlp_norm``, ``w1``, ``w3``, ``w2`` or
``moe_norm``, ``router``, ``gate``, ``up``, ``down`` (with a shared expert
also ``shared_gate``, ``shared_up``, ``shared_down``,
``shared_expert_gate``; with ``relu2`` no ``gate`` and no ``shared_gate``,
ungated no ``shared_expert_gate``); a block of one branch has its branch's
leaves and no others; with ``sandwich_norm`` also ``op_post_norm`` and
``ff_post_norm``, the gains behind the two branches. A looped torso has
``exit_gate`` beside ``embed`` and ``final_norm``. ``init`` draws eight keys
a layer whatever its kind, so a layer's draws do not depend on its
neighbours'; the decay's, the shared expert's, the exit gate's and the taps'
bias's draws come from keys folded off the torso's own (gains draw nothing),
so the older models' trees are bit for bit what they were.

The expert layer: a float32 router over all ``num_experts`` experts,
SwiGLU experts (``relu2``: ``down(relu(up h) ** 2)``). ``softmax`` scores
are a softmax over the experts with the largest ``num_experts_per_tok``
renormalised; ``sigmoid`` scores are one
sigmoid an expert, the largest of score + ``router["bias"]`` selected and
weighed by their *scores* over (their sum + 1e-6). That bias
(``use_expert_bias``) is the one torso state no loss trains: it enters a
top-k only, so its gradient is exactly zero and the optimizer leaves it;
``balance`` moves it after the optimizer step by ``bias_update_rate *
sign(mean(n) - n)``, ``n`` the differentiated pass's load a layer
(``aux["route_counts"]``), and ``aux["bias_swapped"]`` counts the
assignments it changed. The layer is told which experts it holds
(``experts_held``, one chip's share under expert parallelism,
``parallel/partition.expert_share``): it routes over all of them and adds
its own experts' part of the result; on one chip that is the whole layer
without the exchange. No capacity, no dropped token: every assignment to a
held expert is computed whatever the routing. The selection and its
bookkeeping are dense vector work: no sort of a token's experts
(``_largest``), no scalar gather of the selected scores (``_picked``), no
scatter of the assignments' rows (``_places``) or of the selection's
cotangent (``_placed``); what is left index by index is one sort of the
``T * k`` expert numbers (the sorted buffer's order) and the row gathers
into and out of that buffer (``_to_sorted``, ``_from_sorted``). The scope
``torso.route`` is split for a trace reader by what it holds:
``torso.route.norm``, ``.router``, ``.select``, ``.counts``, ``.order``,
``.gather``, ``.combine``.

Tokens are Gato's (Reed et al. 2022, sec. 2.1): mu-law, clip to [-1, 1],
``bins`` uniform bins, on the float32 values (bfloat16 cannot tell 1,024
bins apart); bin ``b`` is row ``b`` of the embedding held here.

Memory. A layer runs one sequence at a time (``lax.map``) and each
sequence is rematerialised on its own in the backward pass, so only the
``[B, T, D]`` float32 layer boundaries are kept and nothing inside a layer
is held for more than one sequence; with no capacity the sorted expert
buffer is sized for every assignment landing here, ``T * k`` rows. The
bfloat16 copies of a layer's matrices are made once a layer and a pass.
At ``keye2``'s 16,384 tokens the scheme is the same and a sequence is four
times as long: a layer boundary is 134 MB a sequence; inside a sequence the
selection is a ``[T, T]`` bool (268 MB, and twice more in the kernel's
block order) and everything score-shaped lives for one block of
``q_chunk_size`` queries (``[512, 16, 16384]`` float32 index scores, 537 MB;
in the differentiated pass ``[8, 512, 16384]`` main scores a key/value
head); the expert layer takes the sequence ``EXPERT_TOKENS`` tokens at a
time, so its sorted buffers are cell 4's (32,768 assignments), not four
times that. At ``lfm2``'s 8,192 tokens a layer boundary is 67 MB a
sequence; inside a sequence the largest arrays are ``in_proj``'s ``[8192,
6144]`` in the compute dtype (the gates and taps are one fused pass over
it) and the dense layer's two ``[8192, 7168]`` float32 products; the
expert layer takes the sequence in two parts of 16,384 assignments. At
``qwen3next``'s 16,384 tokens a DeltaNet sequence's largest arrays are
``in_proj_qkvz``'s ``[16384, 12288]`` in the compute dtype (403 MB) and the
float32 ``q, k, v`` behind the taps (``[16384, 8192]``, 537 MB): those are
made again in the backward pass (``_delta``'s ``front``), so the scan's own
backward runs beside its inputs alone; the scan keeps one ``[32, 128, 128]``
state a group of 4 chunks (64 x 2 MB a sequence, where a state a chunk
would be 537 MB) and a group's intermediates for that group alone
(``ops/delta_rule.py``); the expert layer takes the sequence in four parts
of 40,960 assignments, of which about 1,280 land on the 16 experts held.
At ``nemotronh``'s 8,192 tokens a block boundary is 88 MB a sequence and
there is one a BLOCK, seven for ``MEMEM*E`` (2.5 GB at 4 sequences, where
3.5 layers of two branches would keep half as many); a Mamba sequence's
largest arrays are ``in_proj``'s ``[8192, 10304]`` in the compute dtype (169
MB) and the float32 ``xBC`` behind the taps (``[8192, 6144]``, 201 MB), made
again in the backward pass (``_mamba``'s ``front``); the scan keeps one
``[64, 64, 128]`` state a group of 4 chunks (16 x 2 MB a sequence) and a
group's decay-masked products (``[4, 64, 128, 128]``, 17 MB) for that group
alone (``ops/ssd.py``); the attention block's 16 query heads a key/value head
go to the splash kernel as one group; the expert layer takes the sequence in
two parts of 24,576 assignments, of which about 1,536 land on the 8 experts
held. At ``trinity``'s 16,384 tokens a layer boundary is 134 MB a sequence;
inside a sequence the largest arrays are the ``q`` leaf's ``[16384, 8192]``
float32 output (a head's query and gate, 537 MB), its halves behind the
heads' norm and the rotation (268 MB each) and the dense layer's two
``[16384, 6144]`` float32 products (403 MB each); the expert layer takes
the sequence in four parts of 32,768 assignments, of which about 2,048 land
on the 8 experts held.

A loop (``ouro``: 4 passes of 8 layers on 2 sequences of 4,096 tokens) is a
``lax.scan`` over the passes with the leaves closed over: one pass is
traced and compiled, and the scan's transpose sums each leaf's float32
gradient over its R uses. Inside a pass the scheme is the one above, so what
is kept for the backward pass is one layer boundary a layer AND a pass, ``R
x L`` = 32 of 67 MB (2.1 GB), stacked by the scan; nothing inside a layer
outlives a sequence. The compute-dtype copies of a layer's matrices are made
inside the layer's checkpoint, once a use (R x L times a torso pass, and
again in the backward pass), 103 MB at a time, never all 822 MB at once, and
each use's weight gradient is cast back to float32 before the passes are
summed. PERF.md (PR 41) has the forms that lost: a checkpoint round a pass
(``R + L`` boundaries, one more forward), the casts made once and held, and
four traced copies of the stack.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from d4pg_tpu.ops import attention as attn_ops
from d4pg_tpu.ops import delta_rule as delta_ops
from d4pg_tpu.ops import grouped as grouped_ops
from d4pg_tpu.ops import short_conv as conv_ops
from d4pg_tpu.ops import sparse_attention as sparse_ops
from d4pg_tpu.ops import ssd as ssd_ops

HI = jax.lax.Precision.HIGHEST
MU, M = 100.0, 256.0  # Gato's mu-law
# the sorted expert buffer serves routing up to this multiple of an even
# load before the every-assignment buffer takes over (``TorsoSpec
# .expert_buffer``'s default)
EXPERT_BUFFER = 1.5
# a longer sequence goes through the expert layer this many tokens at a
# time (cell 4's whole sequence): the every-assignment buffer of 16,384
# tokens, 131,072 rows, takes 4.5 GB that the chip does not have
EXPERT_TOKENS = 4096
LAYER_TYPES = ("sliding_attention", "full_attention", "sparse_attention",
               "conv", "linear_attention", "mamba", "moe", "attention")
# blocks that are one branch (Nemotron-H's): an operator OR a feed-forward
# alone, written a character a block in ``hybrid_override_pattern``
PATTERN = {"M": "mamba", "E": "moe", "*": "attention"}
ONE_BRANCH = tuple(PATTERN.values())
ROPED = ("sliding_attention", "full_attention", "sparse_attention")
ROUTER_SCORES = ("softmax", "sigmoid")
HIDDEN_ACTS = ("silu", "relu2")  # gated SwiGLU; down(relu(up h) ** 2)
KEPT = ("delta_kept", "ssd_kept")  # a recurrent operator's mean decay
SA_KEYS = ("indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads",
           "kv_chunk_size", "q_chunk_size", "topk")


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return x


@dataclasses.dataclass(frozen=True)
class TorsoSpec:
    """Sizes of a torso; hashable (it is part of the jit-static config).
    Keys follow the model's published ``config.json`` where it has one."""

    name: str
    tokens: int  # sequence length = the observation's width
    vocab_rows: int  # rows of the embedding held here
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: tuple  # one of LAYER_TYPES per layer
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    experts_held: tuple  # [lo, hi) of the experts this chip holds
    # layer type -> rope block, frozen; a roped type (``ROPED``) is named
    # here, with its block or with an explicit ``None``: no rotary embedding
    rope_parameters: Any = None
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    bins: int = 1024
    sliding_window: int = 0  # 'sliding_attention' layers
    qk_norm: bool = False  # RMSNorm with a gain on every head of q and k
    sa_config: Any = None  # 'sparse_attention' layers: SA_KEYS, frozen
    conv_L_cache: int = 0  # 'conv' layers: taps a channel
    num_dense_layers: int = 0  # leading layers whose feed-forward is dense
    intermediate_size: int = 0  # the dense feed-forward's width
    router_scores: str = "softmax"  # one of ROUTER_SCORES
    use_expert_bias: bool = False  # a bias a layer that enters the selection
    routed_scaling_factor: float = 1.0
    bias_update_rate: float = 0.0  # gamma of the load-balancing rule
    # 'linear_attention' layers (Gated DeltaNet): heads, their widths, taps
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    partial_rotary_factor: float = 1.0  # the share of a head RoPE turns
    attn_output_gate: bool = False  # q twice as wide: sigmoid(gate) * attn
    shared_expert_intermediate_size: int = 0  # 0: no shared expert
    # a looped (depth-recurrent) torso: the whole stack run this many times
    # on its own output with the same leaves, ``final_norm`` behind every
    # pass; from 2 on the torso has an exit gate (``apply``)
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0  # 1: no pass is ever skipped
    exit_entropy_beta: float = 0.0  # weight of the exit entropy in the loss
    # a norm with its own gain behind each operator and feed-forward too:
    # x + Norm(Op(Norm(x))), then x + Norm(FF(Norm(x)))
    sandwich_norm: bool = False
    # one character a block (``PATTERN``): in place of ``layer_types``
    hybrid_override_pattern: str = ""
    # 'mamba' blocks (Mamba-2): heads, their width, the state a head, the
    # groups that share B and C, taps a channel, tokens a chunk of the scan
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 0
    conv_kernel: int = 0
    chunk_size: int = ssd_ops.CHUNK
    mlp_hidden_act: str = "silu"  # the experts' form, one of HIDDEN_ACTS
    shared_expert_gated: bool = True  # a scalar sigmoid gate on the shared
    # the sorted buffer's rows over an even load's (``even_load_rows``): a
    # small share of the experts strays further from even than a large one
    expert_buffer: float = EXPERT_BUFFER
    embedding_multiplier: float = 1.0  # on the embedding's rows (muP's)

    @classmethod
    def from_dict(cls, d: dict) -> "TorsoSpec":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - names)
        if unknown:
            raise ValueError(f"unknown torso keys {unknown}")
        d = dict(d)
        d.setdefault("layer_types", pattern_blocks(
            d.get("hybrid_override_pattern", "")))
        return cls(**{k: _freeze(v) for k, v in d.items()})

    def __post_init__(self):
        if self.name not in TORSOS:
            raise ValueError(f"unknown torso {self.name!r}; one of "
                             f"{sorted(TORSOS)}")
        lo, hi = self.experts_held
        dense_only = self.num_experts == 0  # no router, no experts
        if dense_only:
            if (lo, hi) != (0, 0) \
                    or self.num_dense_layers != len(self.layer_types):
                raise ValueError("a torso without experts holds none "
                                 "(experts_held [0, 0]) and every layer of "
                                 "it is dense (num_dense_layers)")
        elif not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")
        if self.bins > self.vocab_rows:
            raise ValueError(f"{self.bins} bins need as many embedding rows; "
                             f"{self.vocab_rows} are held")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not divide into key/value heads")
        unknown = sorted(set(self.layer_types) - set(LAYER_TYPES))
        if unknown:
            raise ValueError(f"unknown layer types {unknown}; one of "
                             f"{LAYER_TYPES}")
        if "sliding_attention" in self.layer_types \
                and self.sliding_window < 1:
            raise ValueError("sliding_attention layers need sliding_window")
        if "sparse_attention" in self.layer_types:
            sa = dict(self.sa_config or ())
            if sorted(sa) != sorted(SA_KEYS):
                raise ValueError(f"sparse_attention layers need sa_config "
                                 f"with {SA_KEYS}; got {sorted(sa)}")
            if sa["indexer_num_kv_heads"] != 1:
                raise ValueError("the indexer has one key head")
            sparse_ops.block_plan(self.tokens, sa["q_chunk_size"],
                                  sa["kv_chunk_size"])
        if "conv" in self.layer_types and self.conv_L_cache < 1:
            raise ValueError("conv layers need conv_L_cache taps")
        if self.hybrid_override_pattern and self.layer_types \
                != pattern_blocks(self.hybrid_override_pattern):
            raise ValueError(f"layer_types {self.layer_types} are not "
                             f"hybrid_override_pattern "
                             f"{self.hybrid_override_pattern!r}'s blocks")
        if "mamba" in self.layer_types:
            sizes = (self.mamba_num_heads, self.mamba_head_dim,
                     self.ssm_state_size, self.n_groups, self.conv_kernel,
                     self.chunk_size)
            if min(sizes) < 1:
                raise ValueError("mamba blocks need mamba_num_heads, "
                                 "mamba_head_dim, ssm_state_size, n_groups, "
                                 "conv_kernel and chunk_size")
            if self.mamba_num_heads % self.n_groups:
                raise ValueError("mamba heads do not divide into n_groups")
        if set(self.layer_types) & set(ONE_BRANCH) \
                and (dense_only or self.num_dense_layers):
            raise ValueError("blocks of one branch come with experts and "
                             "without leading dense layers")
        if self.expert_buffer < 1.0:
            raise ValueError("expert_buffer is a multiple of an even load: "
                             "at least 1")
        if not self.embedding_multiplier > 0.0:
            raise ValueError("embedding_multiplier scales the embedding: "
                             "above 0")
        if self.mlp_hidden_act not in HIDDEN_ACTS:
            raise ValueError(f"unknown mlp_hidden_act "
                             f"{self.mlp_hidden_act!r}; one of {HIDDEN_ACTS}")
        unroped = sorted(set(self.layer_types) & set(ROPED)
                         - set(dict(self.rope_parameters or ())))
        if unroped:
            raise ValueError(f"rope_parameters has no block for {unroped}")
        if "linear_attention" in self.layer_types:
            sizes = (self.linear_num_key_heads, self.linear_num_value_heads,
                     self.linear_key_head_dim, self.linear_value_head_dim,
                     self.linear_conv_kernel_dim)
            if min(sizes) < 1:
                raise ValueError("linear_attention layers need the five "
                                 "linear_* sizes")
            if self.linear_num_value_heads % self.linear_num_key_heads:
                raise ValueError("value heads do not divide into key heads")
        rotary = self.head_dim * self.partial_rotary_factor
        if not (0 < rotary <= self.head_dim and rotary == int(rotary)
                and int(rotary) % 2 == 0):
            raise ValueError(f"partial_rotary_factor "
                             f"{self.partial_rotary_factor} does not turn a "
                             f"whole even share of {self.head_dim}")
        if self.attn_output_gate and "sparse_attention" in self.layer_types:
            raise ValueError("sparse_attention layers have no output gate")
        if not dense_only and not 0 <= self.num_dense_layers < len(
                self.layer_types):
            raise ValueError(f"num_dense_layers {self.num_dense_layers} "
                             f"leaves no expert layer of "
                             f"{len(self.layer_types)}")
        if self.num_dense_layers and self.intermediate_size < 1:
            raise ValueError("dense layers need intermediate_size")
        if self.router_scores not in ROUTER_SCORES:
            raise ValueError(f"unknown router_scores "
                             f"{self.router_scores!r}; one of "
                             f"{ROUTER_SCORES}")
        if self.use_expert_bias and self.router_scores != "sigmoid":
            raise ValueError("use_expert_bias is the sigmoid router's")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps counts the passes: at least 1")
        if self.total_ut_steps > 1 and (
                self.num_experts or set(self.layer_types)
                & {"sparse_attention", "linear_attention", "mamba"}):
            raise ValueError("a looped torso hands up one latent a pass and "
                             "no counters: its layers are attention or conv "
                             "with a dense feed-forward")
        if self.early_exit_threshold != 1.0:
            raise ValueError("every pass runs for every row "
                             "(early_exit_threshold 1): an exit before the "
                             "last pass is not implemented")
        if self.exit_entropy_beta < 0.0:
            raise ValueError("exit_entropy_beta weighs an entropy bonus")

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def sa(self) -> dict:
        return dict(self.sa_config)

    @property
    def expert_layers(self) -> tuple:
        """Indices of the layers that have the expert layer: a ``moe`` block,
        or a layer of two branches behind the leading dense ones."""
        return tuple(i for i, lt in enumerate(self.layer_types)
                     if lt == "moe" or (lt not in ONE_BRANCH
                                        and i >= self.num_dense_layers))

    @property
    def mamba_widths(self) -> tuple:
        """``(inner width, B or C's width)`` of a ``mamba`` block."""
        return (self.mamba_num_heads * self.mamba_head_dim,
                self.n_groups * self.ssm_state_size)

    @property
    def rotary_dim(self) -> int:
        """How many of a head's ``head_dim`` RoPE turns (the first)."""
        return int(self.head_dim * self.partial_rotary_factor)

    def rope_for(self, layer_type: str) -> dict | None:
        """The layer type's rope block; ``None``: no rotary embedding."""
        block = dict(self.rope_parameters or ()).get(layer_type)
        return None if block is None else dict(block)


def pattern_blocks(pattern: str) -> tuple:
    """``layer_types`` of a ``hybrid_override_pattern``: ``M`` a Mamba-2
    mixer, ``E`` the expert layer, ``*`` attention, each a block of its
    own."""
    unknown = sorted(set(pattern) - set(PATTERN))
    if unknown:
        raise ValueError(f"unknown blocks {unknown} in "
                         f"hybrid_override_pattern {pattern!r}; one of "
                         f"{sorted(PATTERN)}")
    return tuple(PATTERN[c] for c in pattern)


# -- tokens -------------------------------------------------------------------
def tokenise(spec: TorsoSpec, values):
    """Gato's tokens of float32 ``values``: int32 in ``[0, bins)``."""
    v = values.astype(jnp.float32)
    v = jnp.sign(v) * jnp.log(jnp.abs(v) * MU + 1.0) / math.log(M * MU + 1.0)
    v = jnp.clip(v, -1.0, 1.0)
    b = jnp.floor((v + 1.0) * (0.5 * spec.bins)).astype(jnp.int32)
    return jnp.clip(b, 0, spec.bins - 1)


# -- rotary embeddings --------------------------------------------------------
def rope_inv_freq(rope: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """``(inv_freq [head_dim / 2], attention_factor)`` of a rope block:
    ``default`` or ``yarn`` as Hugging Face's ``_compute_yarn_parameters``
    has it."""
    half = head_dim // 2
    base = float(rope["rope_theta"])
    pos = base ** (-2.0 * np.arange(half, dtype=np.float64) / head_dim)
    if rope["rope_type"] == "default":
        return pos, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations: float) -> float:
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 - ramp) * pos + ramp * pos / factor
    return inv, float(rope.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0)


def rope_tables(rope: dict, head_dim: int, t_len: int):
    """``cos, sin`` of shape ``[t_len, head_dim / 2]``, float32."""
    inv, scale = rope_inv_freq(rope, head_dim)
    angle = (jnp.arange(t_len, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32)[None, :])
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def apply_rope(x, cos, sin):
    """Rotate ``x [..., T, D]`` by halves (the Hugging Face pairing:
    element ``i`` with ``i + D / 2``): ``x cos + rotate_half(x) sin``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.concatenate([cos, cos], axis=-1)
            + turned * jnp.concatenate([sin, sin], axis=-1))


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def layer_norm(x, p: dict, eps: float):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"] + p["bias"]


# -- expert layer -------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_sorted(h, top, inv, k: int):
    """Token ``top[r] // k``'s row of ``h [T, D]`` for each of the first
    ``rows`` sorted assignments ``top = order[:rows]``. The backward pass
    is the inverse gather (``inv``, ``_places``: an assignment's row, past
    the buffer where it has none) and a sum over each token's ``k``
    assignments, not a scatter-add."""
    return h[top // k]


def _to_sorted_fwd(h, top, inv, k):
    return h[top // k], (inv, h.shape[0])


def _to_sorted_bwd(k, res, g):
    inv, t_len = res
    back = jnp.take(g, inv, axis=0, mode="fill", fill_value=0)
    back = back.reshape(t_len, k, g.shape[-1])
    return (jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)


@jax.custom_vjp
def _from_sorted(y, top, inv):
    """Sorted rows ``y [rows, D]`` back in assignment order ``[T * k, D]``;
    an assignment sorted past the buffer's ``rows`` reads zero."""
    return jnp.take(y, inv, axis=0, mode="fill", fill_value=0)


def _from_sorted_fwd(y, top, inv):
    return _from_sorted(y, top, inv), (top,)


def _from_sorted_bwd(res, g):
    return g[res[0]], None, None


_from_sorted.defvjp(_from_sorted_fwd, _from_sorted_bwd)


def _largest(x, k: int):
    """``lax.top_k(x [T, E], k)`` without a sort of all ``E``: ``k`` passes
    over ``[T, E]``, each one reduction to the row's (largest value, lowest
    index that holds it) among the positions behind the pass before in
    that order. The same values and indices bit for bit (a tie goes to the
    lower index), for ``x`` without a NaN and above ``-inf``."""
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def ahead(a, b):
        (va, ia), (vb, ib) = a, b
        first = (va > vb) | ((va == vb) & (ia < ib))
        return jnp.where(first, va, vb), jnp.where(first, ia, ib)

    vals, idx, left = [], [], x
    for j in range(k):
        if j:  # what the last pass took, and everything ahead of it, is out
            left = jnp.where((x < top) | ((x == top) & (at > i)), x, -jnp.inf)
        top, i = jax.lax.reduce(
            (left, at), (jnp.asarray(-jnp.inf, x.dtype),
                         jnp.int32(x.shape[-1])), ahead, (1,))
        top, i = top[:, None], i[:, None]
        vals.append(top)
        idx.append(i)
    return jnp.concatenate(vals, axis=-1), jnp.concatenate(idx, axis=-1)


def _placed(g, e, n_exp: int):
    """``g [T, k]`` at the columns ``e [T, k]`` of a ``[T, n_exp]`` of
    zeros, as a compare and a select-sum over ``[T, k, n_exp]``: a row's
    ``e`` are distinct, so each position takes at most one term and the
    values are a scatter-add's. On the chip the scatter-add of 40,960
    scalars into ``[4096, 512]`` takes 200-360 us and this 56-70 (a trace
    books a scatter under no scope of the torso's: PERF.md section 6,
    PR 50)."""
    hit = e[:, :, None] == jnp.arange(n_exp, dtype=e.dtype)
    return jnp.sum(jnp.where(hit, g[:, :, None], 0), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(x, k: int):
    """``lax.top_k`` on ``x [T, E]`` (``_largest``); the values' cotangent
    goes back through ``_placed``, the indices the only residual."""
    return _largest(x, k)


def _top_k_fwd(x, k):
    vals, idx = _largest(x, k)
    return (vals, idx), (idx, x.shape[-1])


def _top_k_bwd(k, res, g):
    idx, n_exp = res
    return (_placed(g[0], idx, n_exp),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


@jax.custom_vjp
def _picked(x, e):
    """``take_along_axis(x [T, E], e [T, k], -1)`` for distinct ``e`` a row,
    as a one-hot select-sum over ``[T, k, E]`` (no scalar gather: 106 us an
    evaluation at ``[4096, 8]`` of 128 where the gather took 260); its
    cotangent goes back through ``_placed``."""
    hit = e[:, :, None] == jnp.arange(x.shape[-1], dtype=e.dtype)
    return jnp.sum(jnp.where(hit, x[:, None, :], 0), axis=-1)


def _picked_fwd(x, e):
    return _picked(x, e), (e, x.shape[-1])


def _picked_bwd(res, g):
    e, n_exp = res
    return _placed(g, e, n_exp), None


_picked.defvjp(_picked_fwd, _picked_bwd)


def _places(e, lo: int, sizes):
    """Each assignment's row in the sorted buffer, ``[T * k]`` in assignment
    order, without the sort: a held expert's offset plus the assignment's
    rank among that expert's, and ``T * k`` (past any buffer: ``mode="fill"``
    reads zero there) for an assignment to an absent expert. A token's
    ``k`` experts are distinct, so the rank is the number of earlier TOKENS
    routed to the expert: a cumulative sum over ``[T, held]``, not over
    ``[T * k, held]``, and no scatter. ``e [T, k]``; ``sizes [held]`` the
    held experts' counts, the first of them expert ``lo``."""
    held = jnp.arange(sizes.shape[0], dtype=e.dtype) + lo
    hit = e[:, :, None] == held  # [T, k, held]
    routed = jnp.any(hit, axis=1).astype(jnp.int32)  # [T, held]
    before = jnp.cumsum(routed, axis=0) - routed
    row = jnp.cumsum(sizes) - sizes + before  # [T, held]
    place = jnp.sum(jnp.where(hit, row[:, None, :], 0), axis=-1)
    return jnp.where(jnp.any(hit, axis=-1), place, e.size).reshape(-1)


def route(spec: TorsoSpec, h, router: dict):
    """The router (``{"kernel": [D, num_experts]}``, with
    ``use_expert_bias`` also ``"bias" [num_experts]``) on float32
    ``h [T, D]``: ``(weights [T, k], experts [T, k] int32, stats)``;
    ``stats["route_counts"] [num_experts]`` int32.

    ``softmax``: softmax over all experts, the ``k`` largest, renormalised.
    ``sigmoid`` (LFM2's): a sigmoid score an expert; the ``k`` largest of
    score + bias are selected and weigh in by their *scores* (the bias
    enters the selection only, so its gradient is exactly zero), divided by
    their sum + 1e-6, times ``routed_scaling_factor``. With a bias
    ``stats["bias_swapped"]`` counts the assignments it changed: selected,
    and not among the ``k`` largest scores.

    The selection is ``_top_k`` (``k`` reductions over ``[T, E]``, a tie to
    the lower index as ``lax.top_k``'s; its cotangent placed by a compare
    and a select-sum, ``_placed``), the selected scores are read by the
    same one-hot (``_picked``): no sort of the experts, no scalar gather
    or scatter.
    A NaN among a token's scores gives unspecified experts (``lax.top_k``
    ranks it first): it comes of a NaN in ``h`` or in the router's leaves,
    which no selection mends. The router's product is float32 at
    ``HIGHEST``."""
    k, n_exp = spec.num_experts_per_tok, spec.num_experts
    with jax.named_scope("torso.route.router"):
        logits = jnp.dot(h, router["kernel"], precision=HI)
    stats = {}
    with jax.named_scope("torso.route.select"):
        if spec.router_scores == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            if spec.use_expert_bias:
                _, e = _top_k(jax.lax.stop_gradient(
                    scores + router["bias"]), k)
                w = _picked(scores, e)
            else:
                w, e = _top_k(scores, k)
            chosen = w
            if spec.norm_topk_prob:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
            w = w * spec.routed_scaling_factor
        else:
            w, e = _top_k(jax.nn.softmax(logits, axis=-1), k)
            if spec.norm_topk_prob:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
        # the weights are made here, once: left to fuse into their readers
        # the compiler lays them and their cotangent k-major, the branches
        # of ``expert_share`` hand the gradient back in float32 and a layer's
        # temporaries pack 0.3-0.5 GB worse (tests/test_torso_v5e_compile)
        w = jax.lax.optimization_barrier(w)
    with jax.named_scope("torso.route.counts"):
        if spec.router_scores == "sigmoid" and spec.use_expert_bias:
            # an assignment is outside the k largest scores when k experts
            # are ahead of it (a tie goes to the lower index, as top_k's):
            # compares, not a second sort
            rivals, mine = scores[:, None, :], chosen[:, :, None]
            first = jnp.arange(n_exp) < e[:, :, None]
            ahead = jnp.sum((rivals > mine) | ((rivals == mine) & first),
                            axis=-1)
            stats["bias_swapped"] = jnp.sum(ahead >= k, dtype=jnp.int32)
        stats["route_counts"] = jnp.sum(
            e.reshape(-1, 1) == jnp.arange(n_exp)[None], axis=0,
            dtype=jnp.int32)
    return w, e.astype(jnp.int32), stats


def even_load_rows(spec: TorsoSpec, t_len: int) -> int:
    """Rows of the sorted buffer that serve a sequence whose routing is
    within ``expert_buffer`` of even: a multiple of the kernels' row tile,
    at most every assignment."""
    every = t_len * spec.num_experts_per_tok
    even = every * spec.n_held / spec.num_experts
    tile = grouped_ops.ROW_TILE
    # static sizes: Python numbers, never traced
    rows = int(spec.expert_buffer * even)  # jaxlint: disable=host-sync-in-jit
    return min(every, -(-rows // tile) * tile)


def expert_share(spec: TorsoSpec, p: dict, h, dtype, grouped: str = "ragged"):
    """This chip's experts' part of the layer for one sequence: float32
    ``h [T, D]`` (normed) -> ``(out [T, D] float32, stats)``, the stats
    ``route``'s.

    Assignments are sorted with the held experts first, so the held ones
    are the first ``n`` rows of the sorted order whatever the routing
    (``order``, the one sort left: ``T * k`` expert numbers; each
    assignment's row the other way is ``_places``, a cumulative sum and no
    scatter); a grouped product multiplies each held expert's rows by its
    matrices.
    Nothing is dropped: the sorted buffer holds every assignment
    (``T * k`` rows) when it must. When the ``n`` landing here fit
    ``even_load_rows`` (the usual case) the same computation runs on that
    many rows instead (``lax.cond``: both are compiled, one runs): gathers
    and elementwise work follow the buffer, not the ``n`` rows in it.

    With ``shared_expert_intermediate_size`` the shared expert (Qwen3-Next's:
    one SwiGLU every token goes through, under a scalar sigmoid gate
    ``h w_s``) is added whole: under expert parallelism every chip computes
    it alike for its own tokens. ``stats["shared_gate"]`` is the gate summed
    over the tokens."""
    lo, hi = spec.experts_held
    k, n_exp = spec.num_experts_per_tok, spec.num_experts
    t_len = h.shape[0]
    with jax.named_scope("torso.route"):
        w, e, stats = route(spec, h, p["router"])
        counts = stats["route_counts"]
    with jax.named_scope("torso.route.order"):
        sizes = counts[lo:hi]
        n_held = jnp.sum(sizes)
        order = jnp.argsort(jnp.mod(e.reshape(-1) - lo, n_exp), stable=True)
        inv = _places(e, lo, sizes)

    def on(rows: int):
        def part(h, w):
            # Rows past the held groups belong to absent experts: the
            # grouped product neither reads nor writes them, so they hold
            # whatever the buffer held (on the chip: anything, NaN
            # included), in the forward products' outputs and in the
            # backward products' input gradients alike. Every product's
            # input and output goes through ``keep``: a select, whose
            # transpose is the same select on the gradient.
            valid = (jnp.arange(rows) < n_held)[:, None]
            keep = lambda a: jnp.where(  # noqa: E731
                valid, a, jnp.zeros((), a.dtype))
            top = order[:rows]
            with jax.named_scope("torso.route.gather"):
                xs = _to_sorted(h.astype(dtype), top, inv, k)
                # each row's router weight, applied where the rows are few
                # (the buffer) and not where they are many (T * k)
                w_rows = _to_sorted(w.reshape(-1, 1), top, inv, 1)
            with jax.named_scope("torso.experts"):
                dot = lambda a, name: keep(grouped_ops.grouped_matmul(  # noqa
                    keep(a), p[name]["kernel"], sizes, impl=grouped))
                made = {name: dot(xs, name) for name in ("gate", "up")
                        if name in p}
                mid = _hidden(spec, lambda name: made[name].astype(
                    jnp.float32), "gate", "up").astype(dtype)
                y = dot(mid, "down")
                y = (y.astype(jnp.float32) * w_rows).astype(dtype)
            with jax.named_scope("torso.route.combine"):
                y = _from_sorted(y, top, inv).reshape(t_len, k, -1)
                return jnp.sum(y, axis=1, dtype=jnp.float32)
        return part

    every, usual = t_len * k, even_load_rows(spec, t_len)
    if usual >= every:
        out = on(every)(h, w)
    else:
        out = jax.lax.cond(n_held <= usual, on(usual), on(every), h, w)
    if spec.shared_expert_intermediate_size:
        with jax.named_scope("torso.shared_expert"):
            hs = h.astype(dtype)
            proj = lambda name: jnp.dot(  # noqa: E731
                hs, p[name]["kernel"], preferred_element_type=jnp.float32)
            gated = spec.shared_expert_gated
            open_ = jax.nn.sigmoid(  # [T, 1]
                proj("shared_expert_gate")) if gated else 1.0
            mid = _hidden(spec, proj, "shared_gate", "shared_up").astype(dtype)
            out = out + open_ * jnp.dot(mid, p["shared_down"]["kernel"],
                                        preferred_element_type=jnp.float32)
            if gated:
                stats["shared_gate"] = jnp.sum(open_)
    return out, stats


def _hidden(spec: TorsoSpec, proj, gate: str, up: str):
    """An expert's hidden activation from its float32 projections
    ``proj(name)``: ``silu(gate) * up``, or with ``relu2`` ``relu(up) ** 2``
    (no gate matrix)."""
    if spec.mlp_hidden_act == "relu2":
        return jnp.square(jax.nn.relu(proj(up)))
    return jax.nn.silu(proj(gate)) * proj(up)


# -- the torso ----------------------------------------------------------------
class SequenceTorso:
    """``init(key) -> params``; ``apply(params, obs [B, tokens]) ->
    (latent [B, hidden_size] float32, aux)``. ``aux["route_counts"] [layers
    with experts, num_experts]`` int32 is how many assignments the router
    gave each expert; with ``use_expert_bias`` ``aux["bias_swapped"] [layers
    with experts]`` int32 counts those the bias changed; with
    ``sparse_attention`` layers and ``train=True`` (the
    differentiated pass) it also holds ``select_counts [sparse layers,
    tokens / kv_chunk_size]`` int32, the selections by block of keys summed
    over queries and sequences, and ``index_loss``, the indexer's alignment
    loss, the mean over layers, sequences and positions. ``params`` is a
    plain dict; matrix leaves are named ``kernel``, norm gains ``scale``."""

    def __init__(self, spec: TorsoSpec, dtype=jnp.float32):
        self.spec = spec
        self.dtype = dtype

    def attention_impl(self) -> str:
        """The splash kernel on a TPU where its tiling takes the sizes,
        the blockwise ``jnp`` form otherwise."""
        fits = attn_ops.splash_fits(self.spec.tokens, self.spec.head_dim)
        return ("splash" if fits and jax.default_backend() == "tpu"
                else "blockwise")

    def sparse_impl(self) -> str:
        """As ``attention_impl``, for the kernels under a run-time mask."""
        fits = sparse_ops.splash_fits(self.spec.tokens, self.spec.head_dim,
                                      self.spec.sa["kv_chunk_size"])
        return ("splash" if fits and jax.default_backend() == "tpu"
                else "blockwise")

    def grouped_impl(self) -> str:
        """The megablox kernels on a TPU where their tiling takes the
        widths, ``jax.lax.ragged_dot`` otherwise (ops/grouped.py)."""
        fits = grouped_ops.megablox_fits(self.spec.hidden_size,
                                         self.spec.moe_intermediate_size)
        return ("megablox" if fits and jax.default_backend() == "tpu"
                else "ragged")

    def init(self, key):
        s = self.spec
        d, hq = s.hidden_size, s.num_attention_heads * s.head_dim
        hkv = s.num_key_value_heads * s.head_dim
        n, f = s.n_held, s.moe_intermediate_size

        def normal(key, shape, fan_in):
            return {"kernel": jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)}

        def decay(i, heads, a_low):
            """Layer ``i``'s ``A_log`` and ``dt_bias`` from keys of their
            own, as the indexer's below; Mamba-2's and the published Gated
            DeltaNet's draw: A ~ U(a_low, 16), dt log-uniform on [1e-3,
            1e-1] behind an inverse softplus."""
            k_a, k_dt = jax.random.split(jax.random.fold_in(key, i + 1), 2)
            dt = jnp.exp(jax.random.uniform(
                k_dt, (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            return {"A_log": {"value": jnp.log(jax.random.uniform(
                        k_a, (heads,), jnp.float32, a_low, 16.0))},
                    "dt_bias": {"value": dt + jnp.log(-jnp.expm1(-dt))}}

        keys = iter(jax.random.split(key, 1 + 8 * len(s.layer_types)))
        gain = lambda n=d: {"scale": jnp.ones((n,), jnp.float32)}  # noqa
        params = {"embed": normal(next(keys), (s.vocab_rows, d), 1.0),
                  "final_norm": gain()}
        for i, layer_type in enumerate(s.layer_types):
            # eight keys a layer whatever leaves it has: a layer's draws do
            # not depend on the kinds of the layers before it
            k_q, k_k, k_v, k_o, k_router, k_gate, k_up, k_down = (
                next(keys) for _ in range(8))
            if layer_type == "moe":
                op = {}
            elif layer_type == "mamba":
                hm, tap = s.mamba_num_heads, s.conv_kernel
                wide, bc = s.mamba_widths
                # the taps' bias: U(-1, 1) / sqrt(taps), a 1-D convolution's
                # usual draw, from a key behind the decay's
                k_bias = jax.random.fold_in(
                    key, 3 * len(s.layer_types) + 2 + i)
                op = {"mamba_norm": gain(),
                      "in_proj": normal(k_q, (d, 2 * wide + 2 * bc + hm), d),
                      "conv": {**normal(k_k, (wide + 2 * bc, tap), tap),
                               "bias": jax.random.uniform(
                                   k_bias, (wide + 2 * bc,), jnp.float32,
                                   -1.0, 1.0) / math.sqrt(tap)},
                      **decay(i, hm, 1.0),
                      "D": {"value": jnp.ones((hm,), jnp.float32)},
                      "out_norm": gain(wide),
                      "out_proj": normal(k_o, (wide, d), wide)}
            elif layer_type == "linear_attention":
                hv, tap = s.linear_num_value_heads, s.linear_conv_kernel_dim
                wk = s.linear_num_key_heads * s.linear_key_head_dim
                wv = hv * s.linear_value_head_dim
                op = {"linear_norm": gain(),
                      "in_proj_qkvz": normal(k_q, (d, 2 * wk + 2 * wv), d),
                      "in_proj_ba": normal(k_k, (d, 2 * hv), d),
                      "conv": normal(k_v, (2 * wk + wv, tap), tap),
                      **decay(i, hv, 1e-6),
                      "out_norm": gain(s.linear_value_head_dim),
                      "out_proj": normal(k_o, (wv, d), wv)}
            elif layer_type == "conv":
                op = {"conv_norm": gain(),
                      "in_proj": normal(k_q, (d, 3 * d), d),
                      "conv": normal(k_k, (d, s.conv_L_cache),
                                     s.conv_L_cache),
                      "out_proj": normal(k_o, (d, d), d)}
            else:
                op = {"attn_norm": gain(),
                      "q": normal(k_q, (
                          d, 2 * hq if s.attn_output_gate else hq), d),
                      "k": normal(k_k, (d, hkv), d),
                      "v": normal(k_v, (d, hkv), d),
                      "o": normal(k_o, (hq, d), hq)}
                if s.qk_norm:
                    op.update(q_norm=gain(s.head_dim),
                              k_norm=gain(s.head_dim))
            if layer_type == "sparse_attention":
                hi, di = s.sa["indexer_num_heads"], s.sa["indexer_head_dim"]
                # keys of their own: the other leaves draw what they drew
                # before this layer type existed
                k_q, k_k, k_w = jax.random.split(
                    jax.random.fold_in(key, i + 1), 3)
                op.update(
                    index_q=normal(k_q, (d, hi * di), d),
                    index_k=normal(k_k, (d, di), d),
                    index_k_norm={**gain(di),
                                  "bias": jnp.zeros((di,), jnp.float32)},
                    index_w=normal(k_w, (d, hi), d))
            if layer_type in ("mamba", "attention"):
                ff = {}  # an operator alone
            elif i < s.num_dense_layers:
                wide = s.intermediate_size
                ff = {"mlp_norm": gain(),
                      "w1": normal(k_gate, (d, wide), d),
                      "w3": normal(k_up, (d, wide), d),
                      "w2": normal(k_down, (wide, d), wide)}
            else:
                router = normal(k_router, (d, s.num_experts), d)
                if s.use_expert_bias:
                    router["bias"] = jnp.zeros((s.num_experts,), jnp.float32)
                swiglu = s.mlp_hidden_act == "silu"  # else two matrices
                ff = {"moe_norm": gain(), "router": router,
                      "up": normal(k_up, (n, d, f), d),
                      "down": normal(k_down, (n, f, d), f)}
                if swiglu:
                    ff["gate"] = normal(k_gate, (n, d, f), d)
                if s.shared_expert_intermediate_size:
                    fs = s.shared_expert_intermediate_size
                    ks = jax.random.split(jax.random.fold_in(
                        key, len(s.layer_types) + 1 + i), 4)
                    ff.update(shared_up=normal(ks[1], (d, fs), d),
                              shared_down=normal(ks[2], (fs, d), fs))
                    if swiglu:
                        ff["shared_gate"] = normal(ks[0], (d, fs), d)
                    if s.shared_expert_gated:
                        ff["shared_expert_gate"] = normal(ks[3], (d, 1), d)
            if s.sandwich_norm:  # gains draw nothing
                ff.update(op_post_norm=gain(), ff_post_norm=gain())
            params[f"layer_{i}"] = {**op, **ff}
        if s.total_ut_steps > 1:
            # a key of its own, behind the decays' and the shared experts'
            params["exit_gate"] = {
                **normal(jax.random.fold_in(
                    key, 2 * len(s.layer_types) + 1), (d, 1), d),
                "bias": jnp.zeros((1,), jnp.float32)}
        return params

    def _post(self, p: dict, out, norm: str):
        """A branch's output on its way to the residual stream: with
        ``sandwich_norm`` through its own RMSNorm (``p[norm]``) first."""
        if self.spec.sandwich_norm:
            return rms_norm(out, p[norm]["scale"], self.spec.rms_norm_eps)
        return out

    def _qkv(self, p: dict, h, layer_type: str):
        """``q [Hkv, G, T, D]`` (scaled), ``k``, ``v [Hkv, T, D]`` of the
        normed ``h [T, D]``: query head i reads key/value head i // G; and
        the output gate's logits ``[T, H * D]`` float32, ``None`` without
        ``attn_output_gate`` (with it a head's ``2 D`` outputs of ``q`` are
        its query, then its gate). RoPE turns the first ``rotary_dim`` of a
        head and passes the rest; a layer type without a rope block turns
        nothing."""
        s, dtype = self.spec, self.dtype
        t_len = h.shape[0]
        hkv, dh = s.num_key_value_heads, s.head_dim
        group = s.num_attention_heads // hkv
        proj = lambda name, out: jnp.dot(  # noqa: E731
            h, p[name]["kernel"], preferred_element_type=out)
        rope = s.rope_for(layer_type)
        if rope is None:  # position comes from elsewhere (Mamba blocks)
            turn = lambda x: x  # noqa: E731
        else:
            cos, sin = rope_tables(rope, s.rotary_dim, t_len)
            if s.rotary_dim == dh:
                turn = lambda x: apply_rope(x, cos, sin)  # noqa: E731
            else:
                turn = lambda x: jnp.concatenate([  # noqa: E731
                    apply_rope(x[..., :s.rotary_dim], cos, sin),
                    x[..., s.rotary_dim:]], axis=-1)
        q, gate = proj("q", jnp.float32), None
        if s.attn_output_gate:
            q = q.reshape(t_len, hkv * group, 2 * dh)
            q, gate = q[..., :dh], q[..., dh:].reshape(t_len, -1)
        q = q.reshape(t_len, hkv, group, dh)
        if s.qk_norm:
            q = rms_norm(q, p["q_norm"]["scale"], s.rms_norm_eps)
        q = (turn(q.transpose(1, 2, 0, 3)) / math.sqrt(dh)).astype(dtype)
        k = proj("k", jnp.float32).reshape(t_len, hkv, dh)
        if s.qk_norm:
            k = rms_norm(k, p["k_norm"]["scale"], s.rms_norm_eps)
        k = turn(k.transpose(1, 0, 2)).astype(dtype)
        v = proj("v", dtype).reshape(t_len, hkv, dh).transpose(1, 0, 2)
        return q, k, v, gate

    def _attend(self, p: dict, x, layer_type: str):
        """Attention of one sequence ``x [T, D]`` added to it."""
        s = self.spec
        full = layer_type != "sliding_attention"
        with jax.named_scope("torso.attn_full" if full
                             else "torso.attn_window"):
            h = rms_norm(x, p["attn_norm"]["scale"], s.rms_norm_eps).astype(
                self.dtype)
            q, k, v, gate = self._qkv(p, h, layer_type)
            a = attn_ops.causal_attention(
                q[None], k[None], v[None],
                window=None if full else s.sliding_window,
                impl=self.attention_impl())[0]
            a = a.transpose(2, 0, 1, 3).reshape(x.shape[0], -1)
            if gate is not None:
                a = (a.astype(jnp.float32)
                     * jax.nn.sigmoid(gate)).astype(self.dtype)
            return x + self._post(p, jnp.dot(
                a, p["o"]["kernel"], preferred_element_type=jnp.float32),
                "op_post_norm")

    def _attend_sparse(self, p: dict, x, train: bool):
        """``_attend`` over the keys the indexer selects: ``(x, (counts
        [T / kv_chunk_size], loss))``, the loss summed over positions and 0
        unless ``train``. The indexer reads the normed input through a
        stop-gradient and the selection is a bool: the main attention's
        gradient cannot reach the indexer, nor the loss anything else. With
        ``train`` the attention's one forward call also hands the loss the
        heads' log-sum-exp (a constant) its target is made from."""
        s, dtype, sa = self.spec, self.dtype, self.spec.sa
        t_len = x.shape[0]
        hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
        with jax.named_scope("torso.attn_sparse"):
            h = rms_norm(x, p["attn_norm"]["scale"], s.rms_norm_eps).astype(
                dtype)
            q, k, v, _gate = self._qkv(p, h, "sparse_attention")
        with jax.named_scope("torso.indexer"):
            hx = jax.lax.stop_gradient(h)
            proj = lambda name: jnp.dot(  # noqa: E731
                hx, p[name]["kernel"], preferred_element_type=jnp.float32)
            cos, sin = rope_tables(s.rope_for("sparse_attention"), di, t_len)
            qi = apply_rope(proj("index_q").reshape(t_len, hi, di).transpose(
                1, 0, 2), cos, sin).transpose(1, 0, 2).astype(dtype)
            ki = apply_rope(layer_norm(proj("index_k"), p["index_k_norm"],
                                       s.rms_norm_eps), cos, sin).astype(dtype)
            wi = proj("index_w") / math.sqrt(hi * di)
            chunks = dict(q_chunk=sa["q_chunk_size"],
                          kv_chunk=sa["kv_chunk_size"])
            keep, counts = sparse_ops.select_keys(qi, ki, wi,
                                                  topk=sa["topk"], **chunks)
        attn = dict(impl=self.sparse_impl(), **chunks)
        if train:  # one forward call serves the output and the loss
            with jax.named_scope("torso.attn_sparse"):
                a, lse = sparse_ops.attention_and_lse(q, k, v, keep, **attn)
            with jax.named_scope("torso.indexer"):
                loss = sparse_ops.alignment_loss(qi, ki, wi, keep, q, k, lse,
                                                 **chunks)
        else:
            with jax.named_scope("torso.attn_sparse"):
                a = sparse_ops.masked_attention(q, k, v, keep, **attn)
            loss = jnp.zeros((), jnp.float32)
        with jax.named_scope("torso.attn_sparse"):
            a = a.transpose(2, 0, 1, 3).reshape(t_len, -1)
            x = x + self._post(p, jnp.dot(
                a, p["o"]["kernel"], preferred_element_type=jnp.float32),
                "op_post_norm")
        return x, (counts, loss)

    def _delta(self, p: dict, x):
        """Qwen3-Next's Gated DeltaNet operator of one sequence ``x [T, D]``
        added to it: ``(x, kept)``, ``kept`` the mean of ``exp(g)`` over
        heads and tokens. ``[q, k, v, z] = h W_qkvz`` in that order
        (``linear_num_key_heads`` heads of ``q`` and ``k``,
        ``linear_num_value_heads`` of ``v`` and ``z``), ``[b, a] = h W_ba``;
        ``q, k, v`` go through a depthwise causal convolution and a SiLU,
        ``q`` and ``k`` are l2-normalised a head, key head ``i`` serves
        value heads ``2 i, 2 i + 1``; the recurrence is
        ``ops/delta_rule.py``'s, in float32; its output is RMS-normalised a
        head, gated by ``silu(z)`` and projected."""
        s, dtype = self.spec, self.dtype
        t_len = x.shape[0]
        hk, hv = s.linear_num_key_heads, s.linear_num_value_heads
        dk, dv = s.linear_key_head_dim, s.linear_value_head_dim
        wk, wv = hk * dk, hv * dv
        with jax.named_scope("torso.deltanet"):
            h = rms_norm(x, p["linear_norm"]["scale"], s.rms_norm_eps).astype(
                dtype)
            qkvz = jnp.dot(h, p["in_proj_qkvz"]["kernel"],
                           preferred_element_type=dtype)
            ba = jnp.dot(h, p["in_proj_ba"]["kernel"],
                         preferred_element_type=jnp.float32)
            z = qkvz[:, 2 * wk + wv:].reshape(t_len, hv, dv)

        # the float32 q, k, v behind the taps are the sequence's largest
        # arrays ([T, 8192], 537 MB each way): made again in the backward
        # pass, so the scan's own backward runs beside its inputs alone
        @jax.checkpoint
        def front(qkv, taps):
            with jax.named_scope("torso.deltanet"):
                qkv = jax.nn.silu(conv_ops.depthwise_causal(qkv, taps))
            with jax.named_scope("torso.delta_scan"):
                unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
                    jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                q = unit(qkv[:, :wk].reshape(t_len, hk, dk)) / math.sqrt(dk)
                k = unit(qkv[:, wk:2 * wk].reshape(t_len, hk, dk))
                return q, k, qkv[:, 2 * wk:].reshape(t_len, hv, dv)

        q, k, v = front(qkvz[:, :2 * wk + wv], p["conv"]["kernel"])
        with jax.named_scope("torso.delta_scan"):
            beta = jax.nn.sigmoid(ba[:, :hv])
            g = -jnp.exp(p["A_log"]["value"]) * jax.nn.softplus(
                ba[:, hv:] + p["dt_bias"]["value"])
            o = delta_ops.gated_delta_rule(q, k, v, g, beta)
            kept = jnp.mean(jnp.exp(g))
        with jax.named_scope("torso.deltanet"):
            y = rms_norm(o, p["out_norm"]["scale"], s.rms_norm_eps) \
                * jax.nn.silu(z.astype(jnp.float32))
            return x + self._post(p, jnp.dot(
                y.astype(dtype).reshape(t_len, wv), p["out_proj"]["kernel"],
                preferred_element_type=jnp.float32), "op_post_norm"), kept

    def _mamba(self, p: dict, x):
        """Nemotron-H's Mamba-2 mixer of one sequence ``x [T, D]`` added to
        it: ``(x, kept)``, ``kept`` the mean of ``exp(dt A)`` over heads and
        tokens. ``[z, xBC, dt] = h W_in`` in that order; ``xBC`` goes through
        a depthwise causal convolution with a bias and a SiLU and splits into
        ``xs`` (``mamba_num_heads`` heads of ``mamba_head_dim``), ``B`` and
        ``C`` (``n_groups`` groups of ``ssm_state_size``, group ``g`` serving
        the heads from ``g H / G`` on); ``dt = softplus(dt + dt_bias)``, ``A =
        -exp(A_log)`` a head; the recurrence is ``ops/ssd.py``'s; its output
        is gated by ``silu(z)``, then RMS-normalised a group of ``inner /
        n_groups`` channels, and projected."""
        s, dtype = self.spec, self.dtype
        t_len = x.shape[0]
        hm, dm = s.mamba_num_heads, s.mamba_head_dim
        wide, bc = s.mamba_widths
        with jax.named_scope("torso.mamba"):
            h = rms_norm(x, p["mamba_norm"]["scale"], s.rms_norm_eps).astype(
                dtype)
            zxbcdt = jnp.dot(h, p["in_proj"]["kernel"],
                             preferred_element_type=dtype)
            z = zxbcdt[:, :wide]

        # the float32 xs, B, C behind the taps ([T, 6144], 201 MB) are made
        # again in the backward pass, as ``_delta``'s ``front``
        @jax.checkpoint
        def front(xbc, conv):
            with jax.named_scope("torso.mamba"):
                xbc = jax.nn.silu(conv_ops.depthwise_causal(
                    xbc, conv["kernel"], conv["bias"]))
                groups = lambda a: a.reshape(  # noqa: E731
                    t_len, s.n_groups, s.ssm_state_size)
                return (xbc[:, :wide].reshape(t_len, hm, dm),
                        groups(xbc[:, wide:wide + bc]),
                        groups(xbc[:, wide + bc:]))

        xs, b, c = front(zxbcdt[:, wide:2 * wide + 2 * bc], p["conv"])
        with jax.named_scope("torso.ssd_scan"):
            dt = jax.nn.softplus(
                zxbcdt[:, 2 * wide + 2 * bc:].astype(jnp.float32)
                + p["dt_bias"]["value"])
            a = -jnp.exp(p["A_log"]["value"])
            y = ssd_ops.ssd(xs, dt, a, b, c, p["D"]["value"], dtype=dtype,
                            chunk=s.chunk_size)
            kept = jnp.mean(jnp.exp(dt * a))
        with jax.named_scope("torso.mamba"):
            y = y.reshape(t_len, wide) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y.reshape(t_len, s.n_groups, -1), 1.0,
                         s.rms_norm_eps).reshape(t_len, wide) \
                * p["out_norm"]["scale"]
            return x + self._post(p, jnp.dot(
                y.astype(dtype), p["out_proj"]["kernel"],
                preferred_element_type=jnp.float32), "op_post_norm"), kept

    def _conv(self, p: dict, x):
        """LFM2's gated short convolution of one sequence ``x [T, D]``
        added to it (ops/short_conv.py)."""
        dtype = self.dtype
        with jax.named_scope("torso.conv"):
            h = rms_norm(x, p["conv_norm"]["scale"],
                         self.spec.rms_norm_eps).astype(dtype)
            bcu = jnp.dot(h, p["in_proj"]["kernel"],
                          preferred_element_type=dtype)
            y = conv_ops.gated_short_conv(bcu, p["conv"]["kernel"])
            return x + self._post(p, jnp.dot(
                y, p["out_proj"]["kernel"],
                preferred_element_type=jnp.float32), "op_post_norm")

    def _mlp(self, p: dict, x):
        """The dense SwiGLU feed-forward of one sequence."""
        with jax.named_scope("torso.mlp"):
            h = rms_norm(x, p["mlp_norm"]["scale"],
                         self.spec.rms_norm_eps).astype(self.dtype)
            proj = lambda name: jnp.dot(  # noqa: E731
                h, p[name]["kernel"], preferred_element_type=jnp.float32)
            mid = (jax.nn.silu(proj("w1")) * proj("w3")).astype(self.dtype)
            return self._post(p, jnp.dot(
                mid, p["w2"]["kernel"], preferred_element_type=jnp.float32),
                "ff_post_norm")

    def _sequence(self, p: dict, x, layer_type: str, dense: bool,
                  train: bool):
        """One layer on one sequence: ``x [T, D] -> (x, stats, selected)``;
        ``stats`` is the router's (none of a dense layer) with a
        ``linear_attention`` layer's ``delta_kept`` beside it, ``selected``
        ``()`` but for a sparse layer. A block of one branch (``PATTERN``)
        runs its operator (``mamba`` with its ``ssd_kept``, ``attention``)
        or the expert layer (``moe``) and nothing else."""
        selected, op_stats = (), {}
        if layer_type == "conv":
            x = self._conv(p, x)
        elif layer_type == "linear_attention":
            x, kept = self._delta(p, x)
            op_stats = {"delta_kept": kept}
        elif layer_type == "mamba":
            x, kept = self._mamba(p, x)
            op_stats = {"ssd_kept": kept}
        elif layer_type == "sparse_attention":
            x, selected = self._attend_sparse(p, x, train)
        elif layer_type != "moe":
            x = self._attend(p, x, layer_type)
        if layer_type in ("mamba", "attention"):
            return x, op_stats, selected
        if dense:
            return x + self._mlp(p, x), op_stats, selected
        with jax.named_scope("torso.route.norm"):
            h = rms_norm(x, p["moe_norm"]["scale"], self.spec.rms_norm_eps)
        out, stats = self._experts(p, h)
        with jax.named_scope("torso.route.norm"):
            out = self._post(p, out, "ff_post_norm")
        return x + out, {**stats, **op_stats}, selected

    def _experts(self, p: dict, h):
        """``expert_share`` of one sequence, ``EXPERT_TOKENS`` at a time
        where it is longer (the layer works token by token, so the parts
        are the whole), each part rematerialised on its own."""
        share = lambda hs: expert_share(  # noqa: E731
            self.spec, p, hs, self.dtype, self.grouped_impl())
        t_len = h.shape[0]
        if t_len <= EXPERT_TOKENS or t_len % EXPERT_TOKENS:
            return share(h)
        out, stats = jax.lax.map(jax.checkpoint(share), h.reshape(
            -1, EXPERT_TOKENS, h.shape[-1]))
        return out.reshape(h.shape), _summed(stats)

    def _layer(self, p: dict, x, layer_type: str, dense: bool, train: bool):
        """One layer on the batch, a sequence at a time. The compute-dtype
        copies of the matrices are made once, here, and live as long as
        the layer; each sequence is rematerialised on its own in the
        backward pass."""
        cast = {name: ({"kernel": leaf["kernel"].astype(self.dtype)}
                       if "kernel" in leaf and name not in ("router", "conv")
                       else leaf)
                for name, leaf in p.items()}
        per_seq = jax.checkpoint(
            lambda xs: self._sequence(cast, xs, layer_type, dense, train))
        x, stats, selected = jax.lax.map(per_seq, x)
        return x, _summed(stats), selected

    def _stack(self, params: dict, x, train: bool):
        """Every layer once, in order, on the batch ``x [B, T, D]``: ``(x,
        stats, selected, kept)``, one entry a layer that has any (``kept``
        by counter: ``KEPT``)."""
        s = self.spec
        stats, selected, kept = [], [], {}
        for i, layer_type in enumerate(s.layer_types):
            layer = jax.checkpoint(
                lambda p, x, lt=layer_type, dense=i < s.num_dense_layers:
                self._layer(p, x, lt, dense, train))
            x, st, sel = layer(params[f"layer_{i}"], x)
            for name in set(st) & set(KEPT):  # a mean a sequence, summed
                kept.setdefault(name, []).append(st.pop(name) / x.shape[0])
            if st:
                stats.append(st)
            if sel:
                selected.append(sel)
        return x, stats, selected, kept

    def _passes(self, params: dict, x):
        """The looped torso: ``total_ut_steps`` passes of ``_stack`` over its
        own output with the same leaves (a ``lax.scan`` with the leaves
        closed over: one pass is compiled, and every leaf's gradient is the
        sum over its uses), ``final_norm`` behind every pass: the normed
        state is what the next pass starts from and what is pooled.
        ``(latents [R, B, D], gate logits [R, B])``."""
        s = self.spec

        def one(x, _):
            x = self._stack(params, x, False)[0]
            with jax.named_scope("torso.exit"):
                x = rms_norm(x, params["final_norm"]["scale"], s.rms_norm_eps)
                return x, jnp.mean(x, axis=1)

        _, latents = jax.lax.scan(one, x, None, length=s.total_ut_steps)
        with jax.named_scope("torso.exit"):
            gate = params["exit_gate"]
            logits = jnp.dot(latents, gate["kernel"], precision=HI)[..., 0]
            return latents, logits + gate["bias"]

    def apply(self, params: dict, obs, train: bool = False):
        s = self.spec
        with jax.named_scope("torso.embed"):
            tokens = tokenise(s, obs)
            x = params["embed"]["kernel"][tokens]
            if s.embedding_multiplier != 1.0:
                x = x * s.embedding_multiplier
        if s.total_ut_steps > 1:
            latents, logits = self._passes(params, x)
            return latents[-1], ({"pass_latents": latents,
                                  "exit_logits": logits} if train else {})
        x, stats, selected, kept = self._stack(params, x, train)
        with jax.named_scope("torso.pool"):
            x = rms_norm(x, params["final_norm"]["scale"], s.rms_norm_eps)
            latent = jnp.mean(x, axis=1)
        aux = {name: jnp.stack([st[name] for st in stats])
               for name in (stats[0] if stats else ())}
        for name, values in kept.items():
            aux[name] = jnp.stack(values)
        if "shared_gate" in aux:  # summed over tokens and sequences
            aux["shared_gate"] = aux["shared_gate"] / (obs.shape[0] * s.tokens)
        if selected and train:
            with jax.named_scope("torso.indexer"):
                aux["select_counts"] = jnp.stack(
                    [jnp.sum(c, axis=0) for c, _loss in selected])
                aux["index_loss"] = jnp.mean(jnp.stack(
                    [loss for _c, loss in selected])) / s.tokens
        return latent, aux

    def balance(self, params: dict, route_counts):
        """The load-balancing rule on the routers' biases (DeepSeek-V3's,
        arXiv:2412.19437 sec. 2.1.2): ``bias += bias_update_rate *
        sign(mean(n) - n)``, ``n`` the ``route_counts [expert layers,
        num_experts]`` of the tokens here. No loss trains the bias and no
        optimizer steps it; a torso without one comes back as it is."""
        s = self.spec
        if not s.use_expert_bias:
            return params
        n = route_counts.astype(jnp.float32)
        move = s.bias_update_rate * jnp.sign(
            jnp.mean(n, axis=-1, keepdims=True) - n)
        out = dict(params)
        for row, i in enumerate(s.expert_layers):
            layer = params[f"layer_{i}"]
            router = layer["router"]
            out[f"layer_{i}"] = {**layer, "router": {
                **router, "bias": router["bias"] + move[row]}}
        return out


def exit_distribution(logits):
    """A looped torso's exit distribution a row from its gate logits ``[R,
    B]``, ``lambda_t = sigmoid(logit_t)``: ``p_t = lambda_t prod_{j<t} (1 -
    lambda_j)`` for ``t < R`` and ``p_R = prod_{j<R} (1 - lambda_j)``, what
    is left (``lambda_R`` is not read); and its entropy ``-sum_t p_t log
    p_t``. ``(p [R, B], entropy [B])``. ``p`` is the products themselves, so
    that it sums to 1 to float32's last bits (the chip's ``exp`` of a summed
    ``log`` is good to five digits); ``log p`` is the sum of log-sigmoids,
    finite where a gate saturates and ``p`` underflows."""
    with jax.named_scope("torso.exit"):
        lam = jax.nn.sigmoid(logits[:-1])
        stay = jnp.cumprod(1.0 - lam, axis=0)
        p = jnp.concatenate(
            [lam * jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]]),
             stay[-1:]])
        log_stay = jnp.cumsum(jax.nn.log_sigmoid(-logits[:-1]), axis=0)
        log_p = jnp.concatenate(
            [jax.nn.log_sigmoid(logits[:-1]) + jnp.concatenate(
                [jnp.zeros_like(log_stay[:1]), log_stay[:-1]]),
             log_stay[-1:]])
        return p, -jnp.sum(p * log_p, axis=0)


def _summed(stats: dict) -> dict:
    """A layer's counters summed over the leading axis ``lax.map`` gave
    them."""
    return {name: jnp.sum(c, axis=0) for name, c in stats.items()}


class TorsoCritic:
    """A torso and the critic head that reads its latent, as one network
    with flax's two calls. Its tree, ``{"params": {"torso": ..., "critic":
    ...}}``, is the only place the torso's parameters live: the actor's
    tree is its head alone, and reads the latent through ``latent``."""

    def __init__(self, torso, head):
        self.torso, self.head = torso, head

    def init(self, key, obs, action):
        k_torso, k_head = jax.random.split(key)
        latent = jnp.zeros((obs.shape[0], self.torso.spec.hidden_size),
                           jnp.float32)
        return {"params": {
            "torso": self.torso.init(k_torso),
            "critic": self.head.init(k_head, latent, action)["params"]}}

    def latent(self, params, obs, train: bool = False):
        """``(latent, aux)`` of the torso in ``params``."""
        return self.torso.apply(params["params"]["torso"], obs, train)

    def balance(self, params, route_counts):
        """``params`` with the torso's routing biases moved by its
        load-balancing rule (``SequenceTorso.balance``)."""
        inner = params["params"]
        return {**params, "params": {**inner, "torso": self.torso.balance(
            inner["torso"], route_counts)}}

    def of_latent(self, params, latent, action, logits: bool = False):
        """The head in ``params`` on a latent."""
        return self.head.apply({"params": params["params"]["critic"]},
                               latent, action, logits)

    def apply(self, params, obs, action, return_logits: bool = False):
        return self.of_latent(params, self.latent(params, obs)[0], action,
                              return_logits)


TORSOS = {"mellum2": SequenceTorso, "keye2": SequenceTorso,
          "lfm2": SequenceTorso, "qwen3next": SequenceTorso,
          "ouro": SequenceTorso, "nemotronh": SequenceTorso,
          "trinity": SequenceTorso}


def build_torso(spec: TorsoSpec, dtype=jnp.float32):
    return TORSOS[spec.name](spec, dtype)
