"""A decoder made of layer kinds: each layer names its mixer (``kda``,
``mla``, ``full``, ``window``, ``cca``, ``mamba`` or ``mla_window``) and its MLP
(``dense``, ``experts``, ``none``: the layer is its mixer alone, or the
pair ``shortcut`` / ``dense_add``: a dense MLP and experts on one normed
input, the experts' sum added a layer later), owns the parameters of those
kinds and keeps the state of its mixer's kind.

Nine families are defined here.  ``bailing_hybrid`` (Ling-3.0-flash and
its -VL sibling's language model): KDA linear attention (``ops/kda.py``)
beside a latent-attention layer every ``layer_group_size`` layers
(``ops/mla.py``), a leading dense SwiGLU layer and then sigmoid-routed
experts with a shared one (``ops/moe.py``), of which this process may
hold a share.  ``mellum`` (Mellum2-12B-A2.5B): grouped-query attention
in two kinds (``ops/gqa.py``), ``window`` layers that see the last
``sliding_window`` positions beside a ``full`` layer every fourth, each
kind with its own rotary frequencies (YaRN on the full layers,
``ops/rope.py``), and softmax-routed experts with neither selection bias
nor shared expert.  ``exaone_moe`` (K-EXAONE-236B-A23B): the same two GQA
kinds with an RMSNorm over each query and key head (QK-norm), rotary
frequencies on the window layers and none at all on the full ones, a
leading dense layer, sigmoid-routed experts with a shared one of which
this process may hold a share, and one multi-token-prediction module
after the stack (:func:`mtp_forward`), which the engine serves as the
draft of its decode step.  ``mistral4`` (Mistral-Small-4-119B-2603):
latent attention as the mixer of EVERY layer, so a slot's state is latent
rows and nothing else; the query goes through a low-rank pair
(``W_qa`` -> RMSNorm -> ``W_qb``), the latent's rotary part takes YaRN
frequencies, the query is scaled by a factor that grows with its position
(``llama_4_scaling_beta``), the output has no gate, and every layer has
softmax-routed experts with a shared one, of which this process may hold
a share.  The ``mla`` kind serves Ling's form and this one from one
function; what differs is data on the configuration (``LatentConfig``).
``zaya`` (ZAYA1-8B): compressed convolutional attention as the mixer of
every layer (the ``cca`` kind, ``ops/cca.py``: q and k latents of 8 and 2
heads through a two-step causal convolution, their un-mixed mean added
back, a length norm, half of each head rotated, half of the value heads
taken from the previous token, then a full layer's attention inside the
latent), one expert a token of 16 chosen by the ZAYA router, an MLP that
is handed the previous layer's router state (``ops/moe.py::route_mlp``:
``forward`` carries that state from layer to layer beside ``x``), and a
head tied to the embedding (``CcaConfig``).
``nemotron_h`` (NVIDIA-Nemotron-3-Super-120B-A12B): a stack whose
published layers are ONE function each, named by a letter of
``hybrid_override_pattern`` and read here as pairs (:func:`pair_pattern`:
a mixer takes the ``E`` that follows it, or nothing): ``M`` a Mamba-2
mixer (the ``mamba`` kind, ``ops/ssm.py``: one projection to a gate, the
convolution's inputs and the heads' steps, a depthwise causal convolution
with a bias, the state-space scan over a float32 state of 64 x 128 a head
in blocks of ``chunk_size`` tokens, or one update of it a decode step, a
gated RMSNorm a group), ``*`` a ``full`` GQA layer that is not rotated,
``E`` sigmoid-routed experts, 22 a token of 512, that work between two
projections in a latent of ``moe_latent_size`` and have no gate
(``W2 relu(W1 u)^2``), beside a shared expert on the hidden state itself;
this process may hold a share of them (``MambaConfig``).  Its prediction
module is not served: a rejected draft would need ``S`` rolled back.
``dots3_note`` (dots3-note-prev's language model): latent attention in
TWO kinds in one stack, so the sizes of a latent layer are a property of
the kind (``LatentSizes``, ``HybridConfig.latent_sizes``) and not of the
model.  Its ``mla`` layers (128 heads over a latent of 512) attend only
the ``index_topk`` rows a learned indexer selects (DeepSeek-V3.2's: a few
small heads score every earlier position against ONE cached index key a
token; ``ops/mla.py::index_scores`` and what follows it); its
``mla_window`` layers (64 heads over a latent of 1,024, another head
size, another rotary base) see the last ``sliding_window`` positions from
a ring of latent rows; both gate each head's output and rescale their
normed latents; a leading dense layer, then sigmoid-routed experts with a
shared one, of which this process may hold a share
(``IndexedLatentConfig``).  Its vision tower, audio encoder and prediction
module have no key in the public config and are not served.
``deepseek_v32`` (DeepSeek-V3.2): the indexed ``mla`` kind in EVERY layer
(no window kind, no gate, no rescale), with ``mistral4``'s YaRN rotation
and ``mscale`` under the latent's rotary part and the indexer's alike;
leading dense layers, then sigmoid-routed experts chosen inside the best
``topk_group`` of ``n_group`` groups, with a shared one, of which this
process may hold a share that is a PART of a group; and one prediction
module whose block is itself such a latent layer, with an indexer, latent
rows and index keys of its own, which the engine serves as the draft of
its decode step: every step verifies two positions a row, each over the
rows it selects for itself (``PredictingLatentConfig``; the step form of
``_mla_mixer``).
``longcat_flash`` (LongCat-Flash-Chat): a published layer is TWO entries
of ``layer_kinds`` and two state entries, ``("mla", "shortcut")`` and
``("mla", "dense_add")``: two latent sublayers (``mistral4``'s low-rank
query and no gate, ``dots3_note``'s rescale of both normed latents, the
plain frequencies), each followed by a dense SwiGLU, and ONE expert layer
that reads the first sublayer's normed post-attention stream beside the
first dense MLP and is added after the second sublayer's dense MLP
(shortcut-connected experts: ``forward`` carries the experts' sum from the
one entry to the next beside ``x``, as it carries a ZAYA router's state;
in a deployment that is the window in which the experts' exchange hides).
Its router has ``zero_experts`` outputs past the ``n_experts`` real ones
(768 = 512 + 256): softmax over them all, a selection bias, 12 a token,
not renormalised; a choice past the real experts is an identity expert
that adds ``w x`` and computes nothing (``ops/moe.py::expert_mlp``,
``zero_from``), so the real experts a token computes vary from 0 to 12
(``ShortcutLatentConfig``).  Its prediction module has no key in the
public config and is not served.
Where a family goes: a stack of DIFFERING kinds is a ``HybridConfig``, and
a new mixer or MLP is a new layer kind here, not another flag on
``LlamaConfig``.  A stack of IDENTICAL llama-shaped layers is a
``LlamaConfig`` (``models/llama.py``), whatever is done with the stack:
Ouro applies its 48 layers four times to a token, which is one more loop
around that file's scan over stacked layers, where ``forward`` here is a
Python loop over the layers with a state entry a layer (192 unrolled
applications in every program, a state tuple of 192).

One ``forward`` serves the three ways the engine calls a model: a cold
batch into fresh state, a chunk of one slot's prompt, and one decode
step over every slot.  It is given, for each row, where its tokens
start and how many of them count; a token that does not count (a padded
position, a row that does not decode) writes no latent row and leaves
the recurrent state exactly as it was.

State of a slot, by the layer's mixer:

* ``kda``: ``S`` (H, d_k, d_v) float32 and ``conv``, the last
  ``conv_kernel - 1`` inputs of the q/k/v convolution.  Fixed size; it
  exists only as of the last token it has seen.
* ``mla``: ``latent`` (T, kv_lora_rank + rope) — rows that grow with the
  tokens and can be cut at any length (a ``longcat_flash`` layer keeps two
  of them, one a sublayer: 2 x 1,280 B a token at the published widths;
  the experts' sum that crosses the second sublayer lives inside one call
  and is no state); with an indexer, ``index_k``
  (T, index_head_dim) beside them: the index key of every position, rows
  of the same sort (written with the latent row, cut anywhere, grafted on
  a prefix hit).
* ``mla_window``: ``ring_latent`` (R, the kind's kv_lora_rank + rope in
  whole lanes), ``R`` = ``sliding_window``: a ring of latent rows, position
  ``p`` in row ``p % R``; like a GQA ring it exists only as of the last
  token written, so a prefix hit takes it from a snapshot.
* ``full``: ``k``, ``v`` (T, KH * head) — rows like the latent ones.
* ``window``: ``ring_k``, ``ring_v`` (R, KH * head), ``R`` =
  ``sliding_window`` whatever the length: position ``p`` lives in row
  ``p % R``.  Like a recurrent state it exists only as of the last token
  written, so a prefix hit takes it from a snapshot.
* ``cca``: BOTH sorts in one layer: ``k``, ``v`` (T, KH * head), rows
  like a full layer's, and as of the last token ``conv0``, ``conv1`` (the
  last inputs of the two convolution steps) and ``v_prev`` (the last
  token's projection for the value heads taken a token late): 5,376 B a
  layer at the published widths.  A prefix hit grafts the rows and takes
  the tails from a snapshot, which holds nothing else.
* ``mamba``: ``ssm`` (H, P, N) float32, the state-space state of every
  head, and ``conv``, the last ``conv_kernel - 1`` inputs of the
  convolution over ``x``, ``B`` and ``C``: 4,194,304 B and 61,440 B a
  layer at the published widths.  Fixed size; like a KDA layer's it
  exists only as of the last token it has seen, and a token that does not
  count moves neither (its step is 0: a decay of exactly 1, nothing added).

A prediction module adds two entries behind the stack's: its block's rows,
of the kind the configuration names (``mtp_kind``: a ``full`` layer's
``k``, ``v``, or an ``mla`` layer's ``latent`` and ``index_k``), and
``h_last`` (D,), the stack's output at the last position it has seen,
which the module needs with the token after it; like a recurrent state it
exists only as of that token.

``ROW_LEAVES`` names the leaves that hold a row a position; every other
leaf is state as of the last token (``RING_LEAVES`` those that are rings).  ``HybridConfig`` holds what every
family has and the KDA and MLA sizes; ``GqaConfig`` adds the GQA sizes,
the rotary parameters of each kind and the routing options;
``LatentConfig`` adds what the ``mistral4`` family's latent layer has;
``CcaConfig`` the ``zaya`` family's sizes, its router's width and its tied
head; ``MambaConfig`` the ``nemotron_h`` family's Mamba-2 sizes, its
experts' latent and their activation; ``IndexedLatentConfig`` the
``dots3_note`` family's indexer, its rescale and its window kind's sizes;
``PredictingLatentConfig`` the ``deepseek_v32`` family's prediction module
over latent rows; ``ShortcutLatentConfig`` the ``longcat_flash`` family's
rescale and its identity experts.

What is read from a family's convention and not from a key of the
public config is listed under ``assumed`` in
``benchmarks/configs/ling-3.0-flash-vl-l7e128.json``,
``benchmarks/configs/mellum2-12b-a2.5b-l12.json``,
``benchmarks/configs/k-exaone-236b-a23b-l5e16.json``,
``benchmarks/configs/mistral-small-4-119b-l6e32.json``,
``benchmarks/configs/zaya1-8b-l20.json`` (which also lists what of ZAYA1
is not served: residual scaling and "MoD" have no key and no equation)
and ``benchmarks/configs/nemotron-3-super-120b-a12b-l11e128.json`` (no
rotation in the attention layers, the order of the Mamba projection's
outputs, the gate before the norm; not served: the prediction module)
and ``benchmarks/configs/dots3-note-prev-l6e32.json`` (the form of the
rescale and of the gate, the indexer's form, norm and rotation, the
window's count; not served: the towers and the prediction module)
and ``benchmarks/configs/deepseek-v3.2-l5e16.json`` (the indexer's rotary
convention, the module's halves, the seeded weights; not served: FP8, more
than one drafted position)
and ``benchmarks/configs/longcat-flash-chat-l4e16.json`` (the layer's
wiring, where the two scales apply, no renormalisation, the rotation over
adjacent pairs, the untied head; not served: the prediction module);
the plain references are ``models/hybrid_reference.py``,
``models/mellum_reference.py``, ``models/exaone_moe_reference.py``,
``models/mistral4_reference.py``, ``models/zaya_reference.py``,
``models/nemotron_h_reference.py``, ``models/dots3_note_reference.py``,
``models/deepseek_v32_reference.py`` and
``models/longcat_flash_reference.py``.

What a row of ``benchmarks/README.md``'s layout table would say of the
newest family (that file is a ``benchmark`` PR's to edit):
``benchmarks/arch/longcat_flash.py`` maps
``configs/longcat-flash-chat-l4e16.json`` to ``ShortcutLatentConfig``
through :func:`from_hf_config` (``num_layers`` 4 -> 8 entries of
``layer_kinds``) and holds its counts (an identity choice costs no bytes
and no operations) and its comparison (``arch/mistral4.py``'s: the prompt
through ``prefill_rows`` in place, its last positions through the decode
step), ``benchmarks/longcat_flash_reference.py`` is the copy of
``models/longcat_flash_reference.py`` that decides its cell's ``correct``,
``traffic/doc-reason.json`` and ``traffic/doc-reason-closed.json``
(DeepSeek-V3.2's) are its mix, ``layer_metrics/decode_zero_choice_pct.py``
reads the counter this family added (``moe_choices_zero``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar, Mapping

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.ops import cca, gqa, gqa_decode, kda, mla, mla_chunk, mla_decode, moe, ssm
from generativeaiexamples_tpu.ops.dispatch import record
from generativeaiexamples_tpu.ops.rope import (
    NO_ROPE, RopeSpec, apply_rope_partial, apply_rope_spec, rope_spec, yarn_mscale,
)

Params = Mapping[str, Any]
F32 = jnp.float32
MIXERS = ("kda", "mla", "full", "window", "cca", "mamba", "mla_window")
# ``none``: the layer is its mixer alone (no norm, no parameters, nothing
# added): a stack whose published layers are ONE function each reads as
# such pairs (``_from_nemotron_h``).  ``shortcut``: a dense MLP AND experts
# on the same normed input, the dense MLP added here and the experts'
# sum handed on as a pending branch; ``dense_add``: a dense MLP, and then
# the pending branch of the layer before is added (LongCat-Flash's
# shortcut-connected experts: the two halves of one published layer).
MLPS = ("dense", "experts", "none", "shortcut", "dense_add")
# The MLP kinds that own experts, and those that own a dense MLP.
EXPERT_MLPS = ("experts", "shortcut")
DENSE_MLPS = ("dense", "shortcut", "dense_add")
# The mixers a prediction module's block may have: kinds whose state is
# rows a position, so that a rejected draft's row is masked by the length
# and written over (a ring or a recurrent state would have to be rolled
# back).  ``HybridConfig.mtp_kind`` says which one a configuration has.
MTP_MIXERS = ("full", "mla")
# State leaves that hold one row a position, which can be cut at any
# token (the others exist only as of the last token written), and those
# that are rings of rows.  Rows run along axis 1 (slot axis 0).
ROW_LEAVES = ("latent", "k", "v", "index_k")
RING_LEAVES = ("ring_k", "ring_v", "ring_latent")
GQA_LEAVES = {"full": ("k", "v"), "window": ("ring_k", "ring_v")}  # a GQA mixer's K and V
# What a ``cca`` layer keeps beside its ``k`` and ``v`` rows, as of the
# last token: the last inputs of its two convolution steps and the last
# token's projection for the shifted value heads.
CCA_TAILS = ("conv0", "conv1", "v_prev")
# Rows of K (and as many of V) the attention layers read from the slots'
# state, by kind; what the window layers would have read as full layers,
# and what the full layers read where every slot's window is read whole
# (a decode step's row walk reads less: ``ops/gqa_decode.py``).  A model
# with such layers returns them after ``moe.COUNTERS``.
ATTN_COUNTERS = ("read_window", "read_full", "dense_window", "dense_full")
# Rows of the latent cache the MLA layers read, and what they would have
# read with every row's whole window (``kv_bucket``) read; of the rows
# read, those a Pallas kernel walked (a prefill chunk's,
# ``ops/mla_chunk.py``, a decode step's, ``ops/mla_decode.py``:
# ``read_latent``'s own count where the gate admits the call, 0 where it
# does not and in every indexed step).  A ``LatentConfig`` model returns
# them after ``moe.COUNTERS``.
LATENT_COUNTERS = ("read_latent", "dense_latent", "kernel_latent")
# What the indexer of an ``mla`` layer did (``IndexedLatentConfig``):
# (query, position) pairs it scored; (query, row) pairs the attention
# scored: in a prefill call every row of every block walked (``read_latent``)
# for every query that counts, kept or not, since the selection is a mask
# on the walk's softmax; in a decode step the latent rows gathered (its
# ``read_latent`` too); index keys read; and the (query, row) pairs the
# queries that count see, which is what they would attend unselected (for
# a decode step: the rows the decoding slots hold).
INDEX_COUNTERS = ("index_pairs", "read_selected", "read_index", "seen_latent")
# Ring rows the ``mla_window`` layers read, and the rows they would have
# read as full layers: ``ATTN_COUNTERS``' names for the GQA rings.
RING_LATENT_COUNTERS = ("read_window", "dense_window")
# Slots whose recurrent state the KDA layers' decode steps read (the rows
# that decode where the step is ``kda.kda_step_rows``, every slot where
# it is XLA's), and slots x KDA layers.  A prefill call works on its own
# rows' state and counts tokens under the same two names: the tokens that
# count whose scan ``kda.kda_chunk_rows`` did (none where it is XLA's
# ``kda_chunked``), and tokens that count x KDA layers.  The ``mamba``
# layers count their slots the same way (every slot: their step is XLA's)
# and add nothing from a prefill call.
STATE_COUNTERS = ("read_state", "dense_state")
# What the ``mamba`` layers' block scan worked on in prefill calls: tokens
# that count, and blocks computed (``ops/ssm.py::blocks_of``, over every
# row of the call, a group's pad rows among them).  A decode step adds to
# neither.
SSM_COUNTERS = ("ssm_tokens", "ssm_blocks")
# What the step form of an indexed ``mla`` layer gathered (one or two
# queries a slot: a decode step, a verify step, a prediction module's
# block beside them): latent rows gathered, every slot and every position
# of the step, decoding or not; and the rows the decoding slots' positions
# that count need between them (the union of a slot's kept sets: what
# one shared gather would fetch).  A prefill call adds to
# neither, so they are not counted by phase (``HybridConfig.step_counters``).
VERIFY_COUNTERS = ("gathered_verify", "needed_verify")


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """What a latent-attention layer is made of.  A property of the layer
    KIND: a stack may hold two kinds of different head counts, ranks, head
    sizes and rotary base (``HybridConfig.latent_sizes``)."""

    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    d_model: int
    # One (mixer, mlp) pair a layer, in order.
    layer_kinds: tuple[tuple[str, str], ...]
    n_heads: int
    # KDA mixer
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kda_gate_floor: float = -5.0
    # MLA mixer
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # MLPs
    d_ff: int = 6144
    moe_d_ff: int = 768
    shared_d_ff: int = 768
    n_experts: int = 512  # the router's outputs
    experts_held: int = 512  # of which this process holds this many ...
    expert_offset: int = 0  # ... starting at this one
    n_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    norm_topk: bool = True
    norm_eps: float = 1e-6
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    # The latent and K/V rows and the convolution tails; the KDA state is
    # float32 whatever this says.  int8 is refused (``state_dtype``).
    kv_dtype: str = "bfloat16"
    # What ``GqaConfig`` makes fields of.  Constants here, so that a
    # configuration without GQA layers is the fields it always was (its
    # ``dataclasses.asdict`` is held to golden values by the benchmark).
    score_function: ClassVar[str] = "sigmoid"
    router_bias: ClassVar[bool] = True
    n_kv_heads: ClassVar[int] = 0
    attn_head_dim: ClassVar[int] = 128
    sliding_window: ClassVar[int] = 0
    rope_full: ClassVar[RopeSpec | None] = None
    rope_window: ClassVar[RopeSpec | None] = None
    qk_norm: ClassVar[bool] = False
    mtp_layers: ClassVar[int] = 0
    # The (mixer, mlp) of a prediction module's block: a full GQA layer
    # with experts, unless the configuration says otherwise
    # (``PredictingLatentConfig``: a latent layer with its own indexer).
    mtp_kind: ClassVar[tuple[str, str]] = ("full", "experts")
    # What ``LatentConfig`` makes fields of: this is Ling's latent layer.
    q_lora_rank: ClassVar[int] = 0
    rope_latent: ClassVar[RopeSpec | None] = None
    attn_scale_beta: ClassVar[float] = 0.0
    softmax_mscale: ClassVar[float] = 1.0
    mla_out_gate: ClassVar[bool] = True
    latent_block: ClassVar[int] = 0
    # What ``IndexedLatentConfig`` makes fields of: no indexer, no rescale
    # of the normed latents, no window layer of latent rows.
    index_topk: ClassVar[int] = 0
    latent_rescale: ClassVar[bool] = False
    window_latent: ClassVar[LatentSizes | None] = None
    # What ``ShortcutLatentConfig`` makes a field of: router outputs past
    # ``n_experts`` that are identity experts (none).
    zero_experts: ClassVar[int] = 0
    # What ``CcaConfig`` makes fields of: a linear router, an untied head.
    router_hidden: ClassVar[int] = 0
    tie_embeddings: ClassVar[bool] = False
    # What ``MambaConfig`` makes fields of: experts on the hidden state
    # itself, with a gate (SwiGLU).
    moe_latent: ClassVar[int] = 0
    expert_act: ClassVar[str] = "swiglu"

    def __post_init__(self) -> None:
        for mixer, mlp in self.layer_kinds:
            if mixer not in MIXERS or mlp not in MLPS:
                raise ValueError(f"unknown layer kind ({mixer!r}, {mlp!r})")
        mlps = [mlp for _, mlp in self.layer_kinds]
        # Each layer's MLP beside the one before it, the ends beside None.
        if any((m == "dense_add") != (before == "shortcut") for m, before in zip(mlps + [None], [None] + mlps)):
            raise ValueError(
                "a 'shortcut' layer hands its experts' sum to the 'dense_add' "
                "layer that follows it, and to no other"
            )
        if self.n_experts % self.n_group:
            raise ValueError("n_group must divide n_experts")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router's outputs")
        if self.score_function not in moe.SCORE_FUNCTIONS:
            raise ValueError(f"unknown score_function {self.score_function!r}")
        if self.has_attn_counters:
            if self.n_kv_heads < 1 or self.n_heads % self.n_kv_heads:
                raise ValueError("n_kv_heads must divide n_heads")
            if self.rope_full is None or (
                self.rope_window is None and not self.layers_of("cca")
                and not self.layers_of("mamba")
            ):
                raise ValueError("a GQA layer kind needs its rotary parameters")
            if self.layers_of("window") and self.sliding_window < 1:
                raise ValueError("a window layer needs sliding_window")
        if self.layers_of("mla_window") and (
            self.window_latent is None or self.sliding_window < 1
        ):
            raise ValueError(
                "a window layer of latent rows needs its sizes (window_latent) "
                "and sliding_window"
            )
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                "more than one prediction module is not served: the decode "
                "step verifies one draft a row"
            )
        if self.mtp_layers and self.mtp_kind[0] not in MTP_MIXERS:
            raise ValueError(
                f"a prediction module's block is a layer whose state is rows a "
                f"position ({', '.join(MTP_MIXERS)}), not {self.mtp_kind[0]!r}: a "
                "rejected draft's row is masked by the length, a ring or a "
                "recurrent state would have to be rolled back"
            )
        if self.mtp_layers and self.mtp_kind[0] == "full" and self.n_kv_heads < 1:
            raise ValueError(
                "a prediction module whose block is a full GQA layer needs the "
                "GQA sizes (n_kv_heads)"
            )

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def router_outputs(self) -> int:
        """The router's width: the published experts, then the identity
        experts, which no chip holds."""
        return self.n_experts + self.zero_experts

    @property
    def has_attn_counters(self) -> bool:
        """A model with K/V rows a position: the GQA kinds, and ``cca``,
        whose rows are read as a full layer's."""
        return any(self.layers_of(m) for m in ("full", "window", "cca"))

    @property
    def row_counters(self) -> tuple[str, ...]:
        """Names of the counters of rows read that ``forward`` returns
        after ``moe.COUNTERS``, the sets the model's kinds have in this
        order: ``ATTN_COUNTERS`` (K/V rows a position), ``STATE_COUNTERS``
        (recurrent state: ``mamba`` layers, and KDA layers where no layer
        has K/V rows: Ling), ``SSM_COUNTERS`` (``mamba`` layers); a
        ``LatentConfig`` model ``LATENT_COUNTERS``."""
        mamba = bool(self.layers_of("mamba"))
        names = ATTN_COUNTERS if self.has_attn_counters else ()
        if mamba or (not names and self.layers_of("kda")):
            names += STATE_COUNTERS
        return names + (SSM_COUNTERS if mamba else ())

    @property
    def rows_only(self) -> bool:
        """True where every leaf of a slot's state holds a row a position
        (latent rows, a full layer's K/V): the state can then be cut at
        any token, and a prefix hit needs no snapshot."""
        return not (
            self.layers_of("kda") or self.layers_of("window")
            or self.layers_of("cca") or self.layers_of("mamba") or self.mtp_layers
            or self.layers_of("mla_window")
        )

    @property
    def draft(self) -> str:
        """What drafts the decode step: ``mtp``, the model's own
        prediction module, where one is held; else nothing."""
        return "mtp" if self.mtp_layers else ""

    @property
    def step_counters(self) -> tuple[str, ...]:
        """Names of the counters that only a step's form adds to, behind
        ``row_counters``: none, or ``VERIFY_COUNTERS``."""
        return ()

    @property
    def n_counters(self) -> int:
        """Entries of ``forward``'s counters: ``moe.COUNTERS``, then
        ``row_counters``, then ``step_counters``."""
        return len(moe.COUNTERS) + len(self.row_counters) + len(self.step_counters)

    def ring_rows(self, max_len: int) -> int:
        """Rows of a window layer's ring: the window, whatever the length
        (a state shorter than the window holds every position)."""
        return min(self.sliding_window, max_len)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def latent_sizes(self, mixer: str = "mla") -> "LatentSizes":
        """The sizes of a latent layer of kind ``mixer``: the model's own
        fields for ``mla``, ``window_latent`` for ``mla_window``."""
        if mixer == "mla_window":
            return self.window_latent
        return LatentSizes(
            n_heads=self.n_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim, v_head_dim=self.v_head_dim,
            rope_theta=self.rope_theta,
        )

    def row_width(self, mixer: str = "mla") -> int:
        """A stored latent row of kind ``mixer``: ``latent_width``, or the
        window kind's latent and rope key in whole lanes."""
        if mixer == "mla_window":
            sz = self.window_latent
            return -(-(sz.kv_lora_rank + sz.qk_rope_head_dim) // 128) * 128
        return self.latent_width

    @property
    def conv_channels(self) -> int:
        return 3 * self.n_heads * self.kda_head_dim

    @property
    def state_dtype(self):
        if self.kv_dtype == "int8":
            raise ValueError(
                "int8 state is not served for this model: a recurrent state "
                "is re-read and re-written by every token, so its rounding "
                "compounds; the latent rows have no quantized attention path"
            )
        return jnp.dtype(self.kv_dtype)

    def layers_of(self, mixer: str) -> list[int]:
        return [i for i, (m, _) in enumerate(self.layer_kinds) if m == mixer]

    def snapshot_bytes(self, max_len: int | None = None) -> int:
        """Bytes of what one slot keeps only as of its last token: the
        recurrent state of the KDA and ``mamba`` layers, the rings of the
        window layers, of K/V or of latent rows (of a state ``max_len``
        long; absent: ``max_seq_len``), the tails of the ``cca`` layers and a prediction
        module's ``h_last``: every
        leaf of ``init_state`` that is no row a position."""
        shapes = jax.eval_shape(
            lambda: init_state(self, 1, self.max_seq_len if max_len is None else max_len)
        )
        return sum(
            leaf.size * leaf.dtype.itemsize
            for layer in shapes for name, leaf in layer.items() if name not in ROW_LEAVES
        )


@dataclasses.dataclass(frozen=True)
class GqaConfig(HybridConfig):
    """A configuration with ``full`` or ``window`` layers: the GQA sizes,
    each kind's rotary parameters, and the routing of a family that is
    not ``bailing_hybrid``'s."""

    # Routing: ``sigmoid`` scores with a selection bias, or ``softmax``
    # over all outputs with none; ``n_group`` 1 is the plain top-k.
    score_function: str = "sigmoid"
    router_bias: bool = True
    n_kv_heads: int = 0
    attn_head_dim: int = 128
    sliding_window: int = 0
    rope_full: RopeSpec | None = None
    rope_window: RopeSpec | None = None
    # An RMSNorm over each query and key head, before the rotation.
    qk_norm: bool = False
    # Prediction modules held behind the stack (0 or 1): a full GQA layer
    # with experts, fed the next token's embedding beside the stack's
    # output; held, it drafts every decode step (``draft``).
    mtp_layers: int = 0


@dataclasses.dataclass(frozen=True)
class LatentConfig(HybridConfig):
    """A configuration whose ``mla`` layers are the ``mistral4`` family's:
    what differs from Ling's latent layer, and the routing options."""

    score_function: str = "sigmoid"
    router_bias: bool = True
    # The query as ``W_qa`` (D, q_lora_rank) -> RMSNorm -> ``W_qb``; 0 is
    # one full-rank ``w_q``.
    q_lora_rank: int = 0
    # The rotation of the latent's rope key and the queries' rope part
    # (``None``: the plain frequencies of ``rope_theta``).
    rope_latent: RopeSpec | None = None
    # The rotated query times ``1 + beta ln(1 + floor(p / original_max))``
    # (``rope_latent``'s original context); 0 is no such scale.
    attn_scale_beta: float = 0.0
    # The softmax scale is (nope + rope)^-1/2 times this squared.
    softmax_mscale: float = 1.0
    # A sigmoid gate a head on the attention's output (Ling's).
    mla_out_gate: bool = True
    # Prefill attends a block of this many latent rows at a time: in
    # ``ops/mla_chunk.py``'s kernel where its gate admits the chunk (bf16
    # state on one TPU device: a block's expansion, scores and
    # probabilities stay in VMEM; 0.37 ms a row-block of 1,024 for 128
    # heads x 256 queries on a v5e, 0.36-0.38 with blocks of 512, and 0.064
    # for the mistral4 family's 32 heads: PERF.md, PR 48), in
    # ``mla.attend_blocks`` where it does not (float32 state, the CPU,
    # several devices).  There a block's float32 scores reach HBM: at the
    # mistral4 family's sizes 32 heads x 256 queries x 1,024 keys = 33.6
    # MB, its expansion 12.6 MB (a chunk program of 8 rows at position
    # 12,288 took 11.8 ms a layer, 12.6 with blocks of 512, 13.8 with
    # blocks of 2,048: PR 38), at the dots3_note family's 134 MB (0.97 ms
    # a row-block, 0.46-0.56 with blocks of 256).
    latent_block: int = 1024
    # A decode step walks each decoding row's blocks of this many latent
    # rows up to its length and reads nothing of a slot that does not
    # decode: in ``ops/mla_decode.py``'s kernel, all rows of the step in
    # one call, where its gate admits the step (bf16 state on one TPU
    # device), in ``mla.attend_absorbed_blocks``, one row after the other,
    # where it does not.  The twin's figures (PR 38), one layer's attention
    # of a step over 16 slots of 32,768 rows on a v5e: 0.31 ms with 4 rows
    # of 14,000 decoding (what the family's cell holds) and 1.04 ms with
    # all 16, where one product over every slot's whole window takes 1.13
    # ms whoever decodes; blocks of 1,024 read 0.38 and 1.29 ms, of 4,096
    # 0.30 and 1.02.  The kernel's (PR 56): 0.097 ms at the 4 rows (the
    # twin beside it 0.308), 0.275 with all 16 (0.935), 0.506 with 16 rows
    # of 28,000 (1.725); blocks of 512 read 0.116 / 0.361 / 0.663, of 1,024
    # 0.102 / 0.287 / 0.529, of 4,096 0.108 / 0.306 / 0.510.
    latent_decode_block: int = 2048

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.latent_block < 1 or self.latent_decode_block < 1:
            raise ValueError(
                "a LatentConfig attends in blocks (latent_block, latent_decode_block "
                ">= 1): its rows are stored in whole lanes, which the whole-window "
                "forms do not read"
            )

    @property
    def row_counters(self) -> tuple[str, ...]:
        return LATENT_COUNTERS

    @property
    def latent_width(self) -> int:
        """A latent row as stored: the latent and the rope key, filled up
        with zero columns to whole lanes of 128.  The chip's default
        layout of a leaf whose rows are no multiple of 128 wide puts the
        POSITIONS minor, and every step program then copies the whole
        state in and out to get at a row (at 16 slots of 32,768 rows of
        320: 2.5 GB of temporaries and 4 GB of traffic a program)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class ShortcutLatentConfig(LatentConfig):
    """A configuration of the ``longcat_flash`` family: a published layer
    is TWO entries of ``layer_kinds``, ``("mla", "shortcut")`` and
    ``("mla", "dense_add")``: two latent sublayers (the ``mistral4``
    family's low-rank query, no gate, plain frequencies, both normed
    latents rescaled as ``dots3_note``'s are) with a dense MLP each, and
    one expert layer that reads the first sublayer's normed post-attention
    stream and is added after the second sublayer's dense MLP
    (``forward`` carries it between the two entries beside ``x``).  Its
    router has ``zero_experts`` outputs past the ``n_experts`` published
    ones; a choice there is an identity expert (``ops/moe.py::expert_mlp``,
    ``zero_from``)."""

    latent_rescale: bool = True
    zero_experts: int = 0


@dataclasses.dataclass(frozen=True)
class IndexedLatentConfig(LatentConfig):
    """A configuration of the ``dots3_note`` family: ``mla`` layers whose
    queries attend only the rows a learned indexer selects, beside
    ``mla_window`` layers, latent attention of OTHER sizes over a ring of
    the last ``sliding_window`` latent rows."""

    # The indexer of an ``mla`` layer (DeepSeek-V3.2's): ``index_n_heads``
    # heads of ``index_head_dim`` score every earlier position from one
    # cached key a token; the ``index_topk`` highest are attended.
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # Both normed latents times ``(d_model / rank)^1/2``
    # (``apply_mla_qkv_lora_rescale``), in either kind.
    latent_rescale: bool = True
    # The ``mla_window`` kind: its own sizes, over the last
    # ``sliding_window`` positions, the query's own among them.
    sliding_window: int = 0
    window_latent: LatentSizes | None = None

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.index_topk < 1 or self.index_n_heads < 1:
            raise ValueError("an indexer keeps index_topk >= 1 rows from index_n_heads >= 1 heads")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("the indexer rotates the first qk_rope_head_dim of index_head_dim")
        if not self.q_lora_rank:
            raise ValueError("the index queries are taken from the query's latent (q_lora_rank)")

    @property
    def row_counters(self) -> tuple[str, ...]:
        return LATENT_COUNTERS + INDEX_COUNTERS + RING_LATENT_COUNTERS


@dataclasses.dataclass(frozen=True)
class PredictingLatentConfig(IndexedLatentConfig):
    """A configuration of the ``deepseek_v32`` family: EVERY layer an
    ``mla`` layer whose queries attend the rows its indexer selects (no
    window kind, no output gate, no rescale; YaRN on the rotary parts and
    its ``mscale`` in the softmax scale, as ``LatentConfig`` has them),
    routing in groups, and one prediction module whose block is itself
    such a layer, with an indexer and rows of its own: held, it drafts
    every decode step, and the step verifies two positions a row, each
    over the rows it selects for itself."""

    latent_rescale: bool = False
    mla_out_gate: bool = False
    # Prediction modules held behind the stack (0 or 1) and the kind of
    # the module's block.
    mtp_layers: int = 0
    mtp_kind: tuple[str, str] = ("mla", "experts")

    @property
    def step_counters(self) -> tuple[str, ...]:
        return VERIFY_COUNTERS


@dataclasses.dataclass(frozen=True)
class CcaConfig(HybridConfig):
    """A configuration whose every layer is the ``zaya`` family's: a
    ``cca`` mixer (its K/V rows are a full GQA layer's, ``n_kv_heads`` of
    ``attn_head_dim``) and experts chosen one a token by the ZAYA router."""

    score_function: str = "softmax"
    # The selection bias ``beta`` of ``argmax(p + beta)``.
    router_bias: bool = True
    n_kv_heads: int = 0
    attn_head_dim: int = 128
    # The rotation of q and k: over the first ``rotary_dim`` values of a head.
    rope_full: RopeSpec | None = None
    rotary_dim: int = 64
    # Kernels of the two causal convolutions over [q ; k]: ``cca_time0``
    # depthwise, ``cca_time1`` a head's channels mixed.
    conv_time0: int = 2
    conv_time1: int = 2
    # The router's own width (``router_hidden_size``).
    router_hidden: int = 256
    # The head is the embedding (``tie_word_embeddings``).
    tie_embeddings: bool = True

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.n_kv_heads % 2:
            raise ValueError(
                "a cca layer takes half of its value heads from the previous "
                "token: n_kv_heads must be even"
            )
        if self.n_experts_per_tok != 1 or self.n_group != 1:
            raise ValueError("the ZAYA router picks one expert a token, of one group")
        if min(self.conv_time0, self.conv_time1) < 2:
            raise ValueError("a causal convolution keeps a tail: cca_time0, cca_time1 >= 2")

    @property
    def cca_channels(self) -> int:
        """The q and k latents side by side: what the convolutions mix."""
        return (self.n_heads + self.n_kv_heads) * self.attn_head_dim


@dataclasses.dataclass(frozen=True)
class MambaConfig(HybridConfig):
    """A configuration of the ``nemotron_h`` family: ``mamba`` mixers
    beside ``full`` GQA layers that are not rotated, each layer a mixer
    alone or a mixer and experts that work in a latent."""

    score_function: str = "sigmoid"
    router_bias: bool = True
    n_kv_heads: int = 0
    attn_head_dim: int = 128
    rope_full: RopeSpec | None = NO_ROPE
    # The Mamba-2 mixer: ``mamba_heads`` of ``mamba_head_dim`` channels
    # (their product is the mixer's inner width), ``B`` and ``C`` of
    # ``ssm_state`` values shared by the heads of each of ``mamba_groups``
    # groups, the scan in blocks of ``ssm_block`` tokens (``chunk_size``).
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_groups: int = 8
    ssm_state: int = 128
    ssm_block: int = 128
    # (time_step_min, time_step_max, time_step_floor): the range that
    # seeded weights draw a head's step bias for; no arithmetic reads it.
    dt_init: tuple[float, float, float] = (0.001, 0.1, 0.0001)
    # The routed experts work on ``h W_dn`` (D -> moe_latent) and their
    # sum goes back through ``W_up``; 0: on the hidden state itself.
    moe_latent: int = 0
    # ``relu2``: ``W2 relu(W1 u)^2``, no gate (routed and shared alike).
    expert_act: str = "relu2"

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.expert_act not in moe.ACTIVATIONS:
            raise ValueError(f"unknown expert activation {self.expert_act!r}")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError("n_groups must divide mamba_num_heads")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_channels(self) -> int:
        """``x``, ``B`` and ``C`` side by side: what the convolution mixes."""
        return self.mamba_inner + 2 * self.mamba_groups * self.ssm_state


def from_hf_config(
    model: Mapping[str, Any],
    *,
    max_len: int,
    expert_offset: int = 0,
    kv_dtype: str = "bfloat16",
    draft: str = "",
) -> HybridConfig:
    """The public ``config.json`` keys -> ``HybridConfig``, by
    ``model_type``: ``mellum`` (:func:`_from_mellum`), ``exaone_moe``
    (:func:`_from_exaone`), ``mistral4`` (:func:`_from_mistral4`), ``zaya``
    (:func:`_from_zaya`), ``nemotron_h`` (:func:`_from_nemotron_h`),
    ``dots3_note`` (:func:`_from_dots3_note`), ``deepseek_v32``
    (:func:`_from_deepseek_v32`), ``longcat_flash``
    (:func:`_from_longcat_flash`), else the
    ``bailing_hybrid`` family, of which the rest speaks.  ``draft`` ``mtp`` holds the model's own prediction
    module and serves it as the decode step's draft; a family without one
    refuses it.

    ``num_experts`` counts the experts held (the chip's share);
    ``num_experts_published`` (absent: the same) the router's outputs.
    ``first_layer`` (absent: 0) is the published index of the first layer
    kept, so that a cut in depth keeps each layer's published kind: layer
    ``i`` is MLA where ``(i + 1) % layer_group_size == 0`` and KDA
    otherwise; the first ``first_k_dense_replace`` layers kept are dense.
    """
    if model.get("model_type") == "exaone_moe":
        return _from_exaone(
            model, max_len=max_len, expert_offset=expert_offset,
            kv_dtype=kv_dtype, draft=draft,
        )
    if model.get("model_type") == "deepseek_v32":
        return _from_deepseek_v32(
            model, max_len=max_len, expert_offset=expert_offset,
            kv_dtype=kv_dtype, draft=draft,
        )
    if draft:
        raise ValueError(
            f"draft {draft!r} is not served for model_type "
            f"{model.get('model_type')!r}: it has no prediction module here"
        )
    if model.get("model_type") == "mellum":
        return _from_mellum(model, max_len=max_len, kv_dtype=kv_dtype)
    if model.get("model_type") == "mistral4":
        return _from_mistral4(
            model, max_len=max_len, expert_offset=expert_offset, kv_dtype=kv_dtype
        )
    if model.get("model_type") == "zaya":
        return _from_zaya(
            model, max_len=max_len, expert_offset=expert_offset, kv_dtype=kv_dtype
        )
    if model.get("model_type") == "nemotron_h":
        return _from_nemotron_h(
            model, max_len=max_len, expert_offset=expert_offset, kv_dtype=kv_dtype
        )
    if model.get("model_type") == "dots3_note":
        return _from_dots3_note(
            model, max_len=max_len, expert_offset=expert_offset, kv_dtype=kv_dtype
        )
    if model.get("model_type") == "longcat_flash":
        return _from_longcat_flash(
            model, max_len=max_len, expert_offset=expert_offset, kv_dtype=kv_dtype
        )
    period = int(model["layer_group_size"])
    first = int(model.get("first_layer", 0))
    dense = int(model["first_k_dense_replace"])
    kinds = tuple(
        (
            "mla" if (first + j + 1) % period == 0 else "kda",
            "dense" if j < dense else "experts",
        )
        for j in range(int(model["num_hidden_layers"]))
    )
    if model.get("score_function", "sigmoid") != "sigmoid":
        raise ValueError("only sigmoid routing scores are served")
    held = int(model["num_experts"])
    return HybridConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=kinds,
        n_heads=int(model["num_attention_heads"]),
        kda_head_dim=int(model["head_dim"]),
        conv_kernel=int(model["short_conv_kernel_size"]),
        kda_gate_floor=float(model["kda_lower_bound"]),
        kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]),
        qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]),
        rope_theta=float(model["rope_theta"]),
        d_ff=int(model["intermediate_size"]),
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=int(model["moe_shared_expert_intermediate_size"]),
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]),
        topk_group=int(model["topk_group"]),
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=bool(model["norm_topk_prob"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def _from_mellum(model: Mapping[str, Any], *, max_len: int, kv_dtype: str) -> GqaConfig:
    """``model_type: mellum``: ``layer_types`` names each layer's mixer
    (``sliding_attention`` -> ``window``, ``full_attention`` -> ``full``),
    ``mlp_layer_types`` its MLP, ``rope_parameters`` the rotary section of
    each kind.  A cut in depth keeps the first ``num_hidden_layers``
    entries of both lists.  Every expert is held (``num_experts`` are the
    router's outputs); routing is the softmax over them, plain top-k."""
    n = int(model["num_hidden_layers"])
    mixers = {"sliding_attention": "window", "full_attention": "full"}
    layer_types, mlp_types = list(model["layer_types"]), list(model["mlp_layer_types"])
    if len(layer_types) < n or len(mlp_types) < n:
        raise ValueError("layer_types and mlp_layer_types name fewer layers than num_hidden_layers")
    kinds = []
    for mixer, mlp in zip(layer_types[:n], mlp_types[:n]):
        if mixer not in mixers:
            raise ValueError(f"layer type {mixer!r} is not served")
        if mlp != "sparse":
            raise ValueError(
                f"mlp_layer_types entry {mlp!r} is not served: this family's "
                "layers are all 'sparse' (experts, no shared one); a 'dense' "
                "SwiGLU of intermediate_size has no layer of the published "
                "model to be checked against"
            )
        kinds.append((mixers[mixer], "experts"))
    if model.get("attention_bias") or model.get("hidden_act", "silu") != "silu":
        raise ValueError("attention biases and activations other than silu are not served")
    rope = model["rope_parameters"]
    experts = int(model["num_experts"])
    return GqaConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=tuple(kinds),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        attn_head_dim=int(model["head_dim"]),
        sliding_window=int(model["sliding_window"]),
        rope_full=rope_spec(rope["full_attention"]),
        rope_window=rope_spec(rope["sliding_attention"]),
        d_ff=int(model["intermediate_size"]),
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=0,
        n_experts=experts,
        experts_held=experts,
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=1,
        topk_group=1,
        routed_scaling=1.0,
        norm_topk=bool(model["norm_topk_prob"]),
        score_function="softmax",
        router_bias=False,
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def _from_exaone(
    model: Mapping[str, Any], *, max_len: int, expert_offset: int, kv_dtype: str,
    draft: str,
) -> GqaConfig:
    """``model_type: exaone_moe``: ``layer_types`` and ``mlp_layer_types``
    name each layer's mixer and MLP (``dense`` for the first
    ``first_k_dense_replace``, then ``sparse``); a cut in depth keeps
    their first ``num_hidden_layers`` entries.  Every GQA layer has
    QK-norm; the one ``rope_parameters`` section rotates the window
    layers, the full layers are not rotated.  Routing is sigmoid scores
    with a selection bias, ``n_group`` groups, renormalised and scaled;
    ``num_experts`` counts the experts held of ``num_experts_published``
    router outputs (absent: the same), as in ``bailing_hybrid``.  With
    ``draft`` ``mtp`` the one prediction module is held; without, it is
    left out."""
    n = int(model["num_hidden_layers"])
    mixers = {"sliding_attention": "window", "full_attention": "full"}
    mlps = {"dense": "dense", "sparse": "experts"}
    layer_types, mlp_types = list(model["layer_types"]), list(model["mlp_layer_types"])
    if len(layer_types) < n or len(mlp_types) < n:
        raise ValueError("layer_types and mlp_layer_types name fewer layers than num_hidden_layers")
    for kind, known in ((layer_types[:n], mixers), (mlp_types[:n], mlps)):
        unknown = sorted(set(kind) - set(known))
        if unknown:
            raise ValueError(f"layer types {unknown} are not served")
    dense = int(model["first_k_dense_replace"])
    if [m == "dense" for m in mlp_types[:n]] != [j < dense for j in range(n)]:
        raise ValueError("mlp_layer_types and first_k_dense_replace disagree")
    rope = model["rope_parameters"]
    if str(rope.get("rope_type", "default")) != "default":
        raise ValueError(
            f"rope_type {rope.get('rope_type')!r} is not served for exaone_moe: "
            "its window layers take the plain frequencies"
        )
    if model.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(
            "scoring_func other than sigmoid is not served for exaone_moe "
            "(a softmax router has no groups: n_group > 1 with softmax is refused)"
        )
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("activations other than silu are not served")
    modules = int(model.get("num_nextn_predict_layers", 0))
    if draft not in ("", "mtp"):
        raise ValueError(f"draft {draft!r} is not served: only the model's own module ('mtp')")
    if draft:
        if modules != 1:
            raise ValueError(
                f"num_nextn_predict_layers {modules} is not served: the decode "
                "step verifies the draft of exactly one prediction module"
            )
        if list(model.get("mtp_layer_types", ["full_attention"])) != ["full_attention"]:
            raise ValueError(
                f"mtp_layer_types {model.get('mtp_layer_types')} is not served: "
                "the module's block is a full_attention layer"
            )
    held = int(model["num_experts"])
    return GqaConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=tuple(
            (mixers[a], mlps[m]) for a, m in zip(layer_types[:n], mlp_types[:n])
        ),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        attn_head_dim=int(model["head_dim"]),
        sliding_window=int(model["sliding_window"]),
        rope_full=NO_ROPE,
        rope_window=rope_spec(rope),
        qk_norm=True,
        mtp_layers=1 if draft else 0,
        d_ff=int(model["intermediate_size"]),
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=int(model["moe_intermediate_size"]) * int(model["num_shared_experts"]),
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]),
        topk_group=int(model["topk_group"]),
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=bool(model["norm_topk_prob"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def _from_mistral4(
    model: Mapping[str, Any], *, max_len: int, expert_offset: int, kv_dtype: str
) -> LatentConfig:
    """``model_type: mistral4``: every layer an ``mla`` mixer with a
    low-rank query and experts with a shared one (``first_k_dense_replace``
    0); ``rope_parameters`` is YaRN over the latent's rotary part, with
    ``mscale_all_dim``'s term squared in the softmax scale and
    ``llama_4_scaling_beta`` the query's position scale.
    ``n_routed_experts`` counts the experts held of
    ``num_experts_published`` router outputs (absent: the same); routing
    is the softmax over them all, plain top-k, renormalised."""
    if int(model.get("first_k_dense_replace", 0)):
        raise ValueError("a leading dense layer is not served for mistral4")
    if not model.get("rope_interleave", True):
        raise ValueError("only the interleaved rotation is served for mistral4")
    if model.get("scoring_func", "softmax") != "softmax":
        raise ValueError("scoring_func other than softmax is not served for mistral4")
    if int(model.get("n_group", 1)) != 1 or int(model.get("topk_group", 1)) != 1:
        raise ValueError("routing groups are not served for mistral4 (softmax, one group)")
    if model.get("attention_bias") or model.get("mlp_bias"):
        raise ValueError("attention and MLP biases are not served")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("activations other than silu are not served")
    if model.get("sliding_window"):
        raise ValueError("a sliding window is not served for mistral4")
    if not model.get("q_lora_rank"):
        raise ValueError("mistral4 is served with a low-rank query (q_lora_rank)")
    rope = dict(model["rope_parameters"])
    spec = rope_spec(rope)
    if spec.rope_type != "yarn":
        raise ValueError("mistral4 is served with YaRN frequencies on the latent's rotary part")
    held = int(model["n_routed_experts"])
    return LatentConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=(("mla", "experts"),) * int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]),
        qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]),
        rope_theta=float(rope["rope_theta"]),
        q_lora_rank=int(model["q_lora_rank"]),
        rope_latent=spec,
        attn_scale_beta=float(rope.get("llama_4_scaling_beta", 0.0)),
        softmax_mscale=yarn_mscale(spec.factor, float(rope.get("mscale_all_dim", 0.0))),
        mla_out_gate=False,
        d_ff=int(model["intermediate_size"]),
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=int(model["moe_intermediate_size"]) * int(model["n_shared_experts"]),
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=1,
        topk_group=1,
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=bool(model["norm_topk_prob"]),
        score_function="softmax",
        router_bias=False,
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def _from_zaya(
    model: Mapping[str, Any], *, max_len: int, expert_offset: int, kv_dtype: str
) -> CcaConfig:
    """``model_type: zaya``: every layer ``hybrid`` (a ``cca`` mixer with
    experts; a cut in depth keeps the first ``num_hidden_layers`` entries
    of ``layer_types``), the rotation of ``rope_parameters["hybrid"]`` over
    ``partial_rotary_factor`` of a head, one expert a token of
    ``num_experts`` held of ``num_experts_published`` router outputs
    (absent: the same) through a router ``router_hidden_size`` wide, and
    the head tied to the embedding or not as the config says."""
    n = int(model["num_hidden_layers"])
    kinds = list(model["layer_types"])[:n]
    if len(kinds) < n or set(kinds) != {"hybrid"}:
        raise ValueError(
            f"layer types {sorted(set(kinds) - {'hybrid'})} are not served for zaya: "
            "every layer is 'hybrid' (no window: 'hybrid_sliding' has no layer of "
            "the published model to be checked against), one for each of "
            "num_hidden_layers"
        )
    if model.get("sliding_window"):
        raise ValueError("a sliding window is not served for zaya")
    if model.get("attention_bias") or model.get("lm_head_bias"):
        raise ValueError("attention and head biases are not served")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("activations other than silu are not served")
    if int(model["num_experts_per_tok"]) != 1:
        raise ValueError("the ZAYA router picks one expert a token (num_experts_per_tok 1)")
    rope = model["rope_parameters"]["hybrid"]
    head = int(model["head_dim"])
    held = int(model["num_experts"])
    return CcaConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=(("cca", "experts"),) * n,
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        attn_head_dim=head,
        rope_full=rope_spec(rope),
        rotary_dim=int(head * float(rope.get(
            "partial_rotary_factor", model.get("partial_rotary_factor", 1.0)))),
        conv_time0=int(model["cca_time0"]),
        conv_time1=int(model["cca_time1"]),
        router_hidden=int(model["router_hidden_size"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        d_ff=int(model["moe_intermediate_size"]),  # no layer is dense
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=0,
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=1,
        n_group=1,
        topk_group=1,
        routed_scaling=1.0,
        norm_topk=False,
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def pair_pattern(pattern: str) -> tuple[tuple[str, str], ...]:
    """A ``hybrid_override_pattern`` (one letter a published layer: ``M``
    a Mamba-2 mixer, ``*`` attention, ``E`` experts; each layer is
    ``x + f(RMSNorm(x))`` with that ONE ``f``) as this module's (mixer,
    MLP) pairs: a mixer takes the ``E`` that follows it, or nothing.  An
    ``E`` with no mixer before it (``EE``, a leading ``E``) has no pair
    and is refused, as is a letter of another kind (``-``, the dense MLP
    of the family's earlier models, which no layer of this one is)."""
    mixers = {"M": "mamba", "*": "full"}
    pairs: list[tuple[str, str]] = []
    open_pair = False  # the last pair may still take an ``E``
    for at, letter in enumerate(pattern):
        if letter in mixers:
            pairs.append((mixers[letter], "none"))
            open_pair = True
        elif letter == "E" and open_pair:
            pairs[-1] = (pairs[-1][0], "experts")
            open_pair = False
        elif letter == "E":
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} does not pair: the 'E' at "
                f"{at} follows no mixer (a layer of experts after another, or "
                "first in the stack, is not served)"
            )
        else:
            raise ValueError(f"layer letter {letter!r} of {pattern!r} is not served")
    return tuple(pairs)


def _from_nemotron_h(
    model: Mapping[str, Any], *, max_len: int, expert_offset: int, kv_dtype: str
) -> MambaConfig:
    """``model_type: nemotron_h``: the first ``num_hidden_layers`` letters
    of ``hybrid_override_pattern`` paired (:func:`pair_pattern`); the
    Mamba-2 sizes; attention without rotation (``rope_theta`` and
    ``partial_rotary_factor`` are carried unused: the family's attention
    layers have no positional embedding); sigmoid routing with a
    selection bias over ``num_experts_published`` outputs (absent: the
    same) of which ``n_routed_experts`` are held, ``relu2`` experts inside
    a latent of ``moe_latent_size``.  The prediction module
    (``num_nextn_predict_layers``) is not served: a rejected draft would
    need the state-space state rolled back."""
    n = int(model["num_hidden_layers"])
    pattern = str(model["hybrid_override_pattern"])
    if len(pattern) < n:
        raise ValueError("hybrid_override_pattern names fewer layers than num_hidden_layers")
    if model.get("mlp_hidden_act", "relu2") != "relu2" or model.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("nemotron_h is served with relu2 experts and a silu mixer")
    if any(model.get(k) for k in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias")):
        raise ValueError("projection biases are not served")
    if not model.get("use_conv_bias", True):
        raise ValueError("the convolution is served with its bias (use_conv_bias)")
    if model.get("sliding_window"):
        raise ValueError("a sliding window is not served for nemotron_h")
    heads, head = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    if heads * head != int(model.get("expand", 2)) * int(model["hidden_size"]):
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x hidden_size")
    held = int(model["n_routed_experts"])
    return MambaConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=pair_pattern(pattern[:n]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        attn_head_dim=int(model["head_dim"]),
        rope_full=NO_ROPE,
        conv_kernel=int(model["conv_kernel"]),
        mamba_heads=heads,
        mamba_head_dim=head,
        mamba_groups=int(model["n_groups"]),
        ssm_state=int(model["ssm_state_size"]),
        ssm_block=int(model["chunk_size"]),
        dt_init=(
            float(model["time_step_min"]), float(model["time_step_max"]),
            float(model["time_step_floor"]),
        ),
        moe_latent=int(model["moe_latent_size"]),
        expert_act="relu2",
        d_ff=int(model["intermediate_size"]),  # no layer is a dense MLP
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=int(model["moe_shared_expert_intermediate_size"])
        * int(model.get("n_shared_experts", 1)),
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]),
        topk_group=int(model["topk_group"]),
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=bool(model["norm_topk_prob"]),
        norm_eps=float(model.get("layer_norm_epsilon", model.get("norm_eps", 1e-5))),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def _from_dots3_note(
    model: Mapping[str, Any], *, max_len: int, expert_offset: int, kv_dtype: str
) -> IndexedLatentConfig:
    """``model_type: dots3_note``: ``layer_types`` names each layer's mixer
    (``full_attention`` -> ``mla`` with the indexer, ``sliding_attention``
    -> ``mla_window`` with the ``swa_*`` sizes over ``sliding_window_size``
    positions); a cut in depth keeps its first ``num_hidden_layers``
    entries.  The first ``first_k_dense_replace`` layers are dense, the
    rest experts with a shared one: sigmoid scores with a selection bias
    (``noaux_tc``), one group, renormalised and scaled.
    ``n_routed_experts`` counts the experts held of
    ``num_experts_published`` router outputs (absent: the same).  Both
    kinds gate each head's output (``headwise``) and rescale their normed
    latents (``apply_mla_qkv_lora_rescale``).  The vision tower, the audio
    encoder and the prediction module have no key here and are not
    served."""
    n = int(model["num_hidden_layers"])
    mixers = {"full_attention": "mla", "sliding_attention": "mla_window"}
    layer_types = list(model["layer_types"])
    if len(layer_types) < n:
        raise ValueError("layer_types names fewer layers than num_hidden_layers")
    unknown = sorted(set(layer_types[:n]) - set(mixers))
    if unknown:
        raise ValueError(f"layer types {unknown} are not served")
    if model.get("scoring_func", "sigmoid") != "sigmoid" or model.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("dots3_note is served with sigmoid scores and a selection bias (noaux_tc)")
    if int(model.get("n_group", 1)) != 1 or int(model.get("topk_group", 1)) != 1:
        raise ValueError(
            "routing groups are not served for dots3_note: its public config has "
            "no n_group / topk_group (one group); the deepseek_v32 family routes "
            "in groups"
        )
    if int(model.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq other than 1 is not served")
    if model.get("attention_bias") or model.get("hidden_act", "silu") != "silu":
        raise ValueError("attention biases and activations other than silu are not served")
    if model.get("rope_scaling"):
        raise ValueError("rope_scaling is not served for dots3_note (the published value is null)")
    for key in ("attention_gate_type", "swa_attention_gate_type"):
        if model.get(key) != "headwise":
            raise ValueError(f"{key} {model.get(key)!r} is not served: a sigmoid gate a head")
    if not model.get("q_lora_rank") or not model.get("swa_q_lora_rank"):
        raise ValueError("dots3_note is served with low-rank queries (q_lora_rank, swa_q_lora_rank)")
    if not int(model.get("index_topk", 0)):
        raise ValueError("dots3_note's full layers attend what an indexer selects (index_topk)")
    dense = int(model["first_k_dense_replace"])
    held = int(model["n_routed_experts"])
    return IndexedLatentConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=tuple(
            (mixers[kind], "dense" if j < dense else "experts")
            for j, kind in enumerate(layer_types[:n])
        ),
        n_heads=int(model["num_attention_heads"]),
        kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]),
        qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]),
        rope_theta=float(model["rope_theta"]),
        q_lora_rank=int(model["q_lora_rank"]),
        mla_out_gate=True,
        index_n_heads=int(model["index_n_heads"]),
        index_head_dim=int(model["index_head_dim"]),
        index_topk=int(model["index_topk"]),
        latent_rescale=bool(model["apply_mla_qkv_lora_rescale"]),
        sliding_window=int(model["sliding_window_size"]),
        window_latent=LatentSizes(
            n_heads=int(model["swa_num_attention_heads"]),
            q_lora_rank=int(model["swa_q_lora_rank"]),
            kv_lora_rank=int(model["swa_kv_lora_rank"]),
            qk_nope_head_dim=int(model["swa_qk_nope_head_dim"]),
            qk_rope_head_dim=int(model["swa_qk_rope_head_dim"]),
            v_head_dim=int(model["swa_v_head_dim"]),
            rope_theta=float(model["swa_rope_theta"]),
        ),
        d_ff=int(model["intermediate_size"]),
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=int(model["moe_intermediate_size"]) * int(model["n_shared_experts"]),
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=1,
        topk_group=1,
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=bool(model["norm_topk_prob"]),
        score_function="sigmoid",
        router_bias=True,
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def _from_deepseek_v32(
    model: Mapping[str, Any], *, max_len: int, expert_offset: int, kv_dtype: str,
    draft: str,
) -> PredictingLatentConfig:
    """``model_type: deepseek_v32``: every layer an ``mla`` mixer with a
    low-rank query and the indexer (``index_n_heads`` heads of
    ``index_head_dim`` keep ``index_topk`` rows a query); ``rope_scaling``
    is YaRN over the rotary parts (the latent's and the indexer's) with
    ``mscale_all_dim``'s term squared in the softmax scale, as
    :func:`_from_mistral4` builds both; no output gate, no rescale, no
    window.  The first ``first_k_dense_replace`` layers kept are dense,
    the rest experts with a shared one: sigmoid scores with a selection
    bias (``noaux_tc``) in ``n_group`` groups of which ``topk_group`` are
    kept, renormalised and scaled.  ``n_routed_experts`` counts the
    experts held of ``num_experts_published`` router outputs (absent: the
    same); a share may be a part of a group.  With ``draft`` ``mtp`` the
    one prediction module is held, its block a layer of the same kind with
    its own indexer; without, it is left out."""
    if model.get("quantization_config") or str(model.get("torch_dtype", "bfloat16")).startswith("float8"):
        raise ValueError(
            "FP8 weights, rows or index keys are not served for deepseek_v32: a "
            "precision, not an equation; bf16 here"
        )
    if model.get("scoring_func", "sigmoid") != "sigmoid" or model.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("deepseek_v32 is served with sigmoid scores and a selection bias (noaux_tc)")
    if int(model.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq other than 1 is not served")
    if model.get("attention_bias") or model.get("hidden_act", "silu") != "silu":
        raise ValueError("attention biases and activations other than silu are not served")
    if not model.get("q_lora_rank"):
        raise ValueError("deepseek_v32 is served with a low-rank query (q_lora_rank)")
    if not int(model.get("index_topk", 0)):
        raise ValueError("deepseek_v32's layers attend what an indexer selects (index_topk)")
    scaling = dict(model.get("rope_scaling") or {})
    kind = str(scaling.get("rope_type", scaling.get("type", "default")))
    if kind != "yarn":
        raise ValueError("deepseek_v32 is served with YaRN frequencies on the rotary parts (rope_scaling)")
    spec = rope_spec({**scaling, "rope_type": kind, "rope_theta": model["rope_theta"]})
    modules = int(model.get("num_nextn_predict_layers", 0))
    if draft not in ("", "mtp"):
        raise ValueError(f"draft {draft!r} is not served: only the model's own module ('mtp')")
    if draft and modules != 1:
        raise ValueError(
            f"num_nextn_predict_layers {modules} is not served: the decode "
            "step verifies the draft of exactly one prediction module"
        )
    dense = int(model["first_k_dense_replace"])
    held = int(model["n_routed_experts"])
    return PredictingLatentConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=tuple(
            ("mla", "dense" if j < dense else "experts")
            for j in range(int(model["num_hidden_layers"]))
        ),
        n_heads=int(model["num_attention_heads"]),
        kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]),
        qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]),
        rope_theta=float(model["rope_theta"]),
        q_lora_rank=int(model["q_lora_rank"]),
        rope_latent=spec,
        softmax_mscale=yarn_mscale(spec.factor, float(scaling.get("mscale_all_dim", 0.0))),
        index_n_heads=int(model["index_n_heads"]),
        index_head_dim=int(model["index_head_dim"]),
        index_topk=int(model["index_topk"]),
        mtp_layers=1 if draft else 0,
        d_ff=int(model["intermediate_size"]),
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=int(model["moe_intermediate_size"]) * int(model["n_shared_experts"]),
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]),
        topk_group=int(model["topk_group"]),
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=bool(model["norm_topk_prob"]),
        score_function="sigmoid",
        router_bias=True,
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


def _from_longcat_flash(
    model: Mapping[str, Any], *, max_len: int, expert_offset: int, kv_dtype: str
) -> ShortcutLatentConfig:
    """``model_type: longcat_flash``: ``num_layers`` counts published
    layers, each two ``mla`` sublayers (a low-rank query, both normed
    latents times ``(hidden_size / rank)^1/2``: ``mla_scale_q_lora``,
    ``mla_scale_kv_lora``; the plain frequencies of ``rope_theta``; no
    gate) with a dense SwiGLU of ``ffn_hidden_size`` each, and one expert
    layer of ``expert_ffn_hidden_size`` between them: two entries of
    ``layer_kinds`` and two state entries a published layer.  The router
    has ``num_experts_published + zero_expert_num`` outputs (softmax over
    them all, a selection bias, ``moe_topk`` a token, not renormalised,
    times ``routed_scaling_factor``), of which ``n_routed_experts`` real
    ones are held from ``expert_offset`` on and the last
    ``zero_expert_num`` are identity experts.  The prediction module has
    no key here and is not served."""
    if str(model.get("attention_method", "MLA")) != "MLA":
        raise ValueError("longcat_flash is served with latent attention (attention_method MLA)")
    if int(model.get("zero_expert_num", 0)) and model.get("zero_expert_type") != "identity":
        raise ValueError(
            f"zero_expert_type {model.get('zero_expert_type')!r} is not served: an "
            "identity expert adds its weight times the token"
        )
    if bool(model.get("mla_scale_q_lora")) != bool(model.get("mla_scale_kv_lora")):
        raise ValueError("mla_scale_q_lora and mla_scale_kv_lora are served both on or both off")
    if model.get("attention_bias") or model.get("hidden_act", "silu") != "silu":
        raise ValueError("attention biases and activations other than silu are not served")
    if not model.get("q_lora_rank"):
        raise ValueError("longcat_flash is served with a low-rank query (q_lora_rank)")
    if model.get("rope_scaling"):
        raise ValueError("rope_scaling is not served for longcat_flash (the plain frequencies)")
    held = int(model["n_routed_experts"])
    return ShortcutLatentConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=(("mla", "shortcut"), ("mla", "dense_add")) * int(model["num_layers"]),
        n_heads=int(model["num_attention_heads"]),
        kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]),
        qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]),
        rope_theta=float(model["rope_theta"]),
        q_lora_rank=int(model["q_lora_rank"]),
        mla_out_gate=False,
        latent_rescale=bool(model.get("mla_scale_kv_lora")),
        zero_experts=int(model.get("zero_expert_num", 0)),
        d_ff=int(model["ffn_hidden_size"]),
        moe_d_ff=int(model["expert_ffn_hidden_size"]),
        shared_d_ff=0,
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["moe_topk"]),
        n_group=1,
        topk_group=1,
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=False,
        score_function="softmax",
        router_bias=True,
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


# -- parameters ---------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, scale, shape, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def _layer_shapes(cfg: HybridConfig, mixer: str, mlp: str) -> dict:
    """name -> (shape, init) for one layer; init is a fan-in for a normal
    draw, a constant, ``"bias"`` (a normal draw of 0.1: not zero, so that
    tests see it), ``"gamma"`` (the ZAYA router's weight of the layer
    before: uniform in 0.25-0.75, so that leaving it out is seen), or a
    ``mamba`` head's ``"a_log"`` (the log of a uniform draw in 1-16) and
    ``"dt_bias"`` (the inverse softplus of a log-uniform draw in
    ``cfg.dt_init``'s range, so that a head's decay a token lies in
    0.2-0.999).  A layer that is its mixer alone has no ``mlp_norm``."""
    D, H, K = cfg.d_model, cfg.n_heads, cfg.kda_head_dim
    shapes: dict = {"attn_norm": ((D,), 1.0)}
    if mlp != "none":
        shapes["mlp_norm"] = ((D,), 1.0)
    if mixer == "kda":
        shapes.update(
            w_qkv=((D, 3 * H * K), D),
            conv_w=((cfg.conv_kernel, 3 * H * K), cfg.conv_kernel),
            w_f=((D, H * K), D),
            a_log=((H,), 0.0),
            dt_bias=((H, K), 0.0),
            w_b=((D, H), D),
            w_g=((D, H * K), D),
            o_norm=((K,), 1.0),
            w_o=((H * K, D), H * K),
        )
    elif mixer in ("full", "window"):
        hd, KH = cfg.attn_head_dim, cfg.n_kv_heads
        shapes.update(
            w_qkv=((D, (H + 2 * KH) * hd), D),  # q heads, then k, then v
            w_o=((H * hd, D), H * hd),
        )
        if cfg.qk_norm:
            shapes.update(q_norm=((hd,), 1.0), k_norm=((hd,), 1.0))
    elif mixer == "cca":
        hd, KH, C = cfg.attn_head_dim, cfg.n_kv_heads, cfg.cca_channels
        shapes.update(
            # W_q, W_k, then the values' two halves: W_v1 (the current
            # token's heads) and W_v2 (the heads taken a token late).
            w_qkv=((D, C + KH * hd), D),
            conv0_w=((cfg.conv_time0, C), cfg.conv_time0),
            conv0_b=((C,), "bias"),
            conv1_w=((cfg.conv_time1, C // hd, hd, hd), cfg.conv_time1 * hd),
            conv1_b=((C,), "bias"),
            k_temp=((KH,), 1.0),  # tau: the learned temperature a key head
            w_o=((H * hd, D), H * hd),
        )
    elif mixer == "mamba":
        MH, inner, C = cfg.mamba_heads, cfg.mamba_inner, cfg.mamba_conv_channels
        shapes.update(
            # the gate z, then what the convolution mixes (x, B, C), then dt
            w_in=((D, inner + C + MH), D),
            conv_w=((cfg.conv_kernel, C), cfg.conv_kernel),
            conv_b=((C,), "bias"),
            ssm_a_log=((MH,), "a_log"),
            ssm_dt_bias=((MH,), "dt_bias"),
            ssm_d=((MH,), "ones_f32"),
            ssm_norm=((inner,), 1.0),
            w_out=((inner, D), inner),
        )
    else:  # ``mla`` or ``mla_window``: a latent layer of its kind's sizes
        sz = cfg.latent_sizes(mixer)
        H, q_rank, rank = sz.n_heads, sz.q_lora_rank, sz.kv_lora_rank
        qk = sz.qk_nope_head_dim + sz.qk_rope_head_dim
        # What multiplies a RESCALED latent (root mean square (D / rank)^1/2,
        # not 1) draws as from a fan-in of D: rank values that count as D,
        # so that a seeded layer's scores have the spread a layer without
        # the rescale has (a checkpoint's matrices were trained under it).
        fan_q, fan_kv = (D, D) if cfg.latent_rescale else (q_rank, rank)
        if q_rank:
            shapes.update(
                w_qa=((D, q_rank), D),
                q_norm=((q_rank,), 1.0),
                w_qb=((q_rank, H * qk), fan_q),
            )
        else:
            shapes.update(w_q=((D, H * qk), D))
        shapes.update(
            w_kva=((D, rank + sz.qk_rope_head_dim), D),
            kv_norm=((rank,), 1.0),
            w_kvb=((rank, H * (sz.qk_nope_head_dim + sz.v_head_dim)), fan_kv),
        )
        if cfg.mla_out_gate:
            shapes.update(w_gate=((D, H), D))
        shapes.update(w_o=((H * sz.v_head_dim, D), H * sz.v_head_dim))
        if mixer == "mla" and cfg.index_topk:
            # The indexer: queries from the query's latent, one key a token
            # (a LayerNorm with a bias over it), a weight a head.
            HI, dI = cfg.index_n_heads, cfg.index_head_dim
            shapes.update(
                w_qi=((q_rank, HI * dI), fan_q),
                w_ki=((D, dI), D),
                ki_norm=((dI,), 1.0),
                ki_norm_b=((dI,), "bias"),
                w_wi=((D, HI), D),
            )
    if mlp in DENSE_MLPS:
        shapes.update(w_gu=((D, 2 * cfg.d_ff), D), w_down=((cfg.d_ff, D), cfg.d_ff))
    if mlp in EXPERT_MLPS:
        F, Fs, E = cfg.moe_d_ff, cfg.shared_d_ff, cfg.experts_held
        R = cfg.router_hidden
        if R:
            shapes.update(
                router_down=((D, R), D), router_down_b=((R,), "bias"),
                router_gamma=((), "gamma"), router_norm=((R,), 1.0),
                router_w1=((R, R), R), router_b1=((R,), "bias"),
                router_w2=((R, R), R), router_b2=((R,), "bias"),
                router_w3=((R, cfg.n_experts), R), router_b3=((cfg.n_experts,), "bias"),
            )
        else:
            shapes.update(router=((D, cfg.router_outputs), D))
        if cfg.router_bias:
            shapes.update(router_bias=((cfg.router_outputs,), 0.0))
        # The width the routed experts work in: a latent, or the stream's.
        De = cfg.moe_latent or D
        if cfg.moe_latent:
            shapes.update(w_lat_down=((D, De), D), w_lat_up=((De, D), De))
        gated = cfg.expert_act == "swiglu"  # gate and up side by side, or up alone
        shapes.update(
            {"w_gu_e" if gated else "w_up_e": ((E, De, (1 + gated) * F), De)},
            w_down_e=((E, F, De), F),
        )
        if Fs:
            shapes.update(
                {"w_gu_s" if gated else "w_up_s": ((D, (1 + gated) * Fs), D)},
                w_down_s=((Fs, D), Fs),
            )
    return shapes


def init_params(cfg: HybridConfig, key: jax.Array) -> Params:
    """Random parameters, built leaf by leaf in the serving dtype: a
    float32 copy of one layer's experts would not fit beside the rest."""
    dtype = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 32 * cfg.n_layers + 8))

    def leaf(name, shape, init):
        if name == "router_bias":
            # Non-zero, so that tests tell selection from weighting; a
            # served model's is balanced (``balance_router_biases``).
            return _normal(next(keys), 0.05, shape, F32)
        if name in ("a_log", "dt_bias"):
            return _normal(next(keys), 0.5, shape, F32)
        if init == "bias":
            return _normal(next(keys), 0.1, shape, dtype)
        if init == "gamma":
            return jax.random.uniform(next(keys), shape, F32, 0.25, 0.75)
        if init == "a_log":
            return jnp.log(jax.random.uniform(next(keys), shape, F32, 1.0, 16.0))
        if init == "dt_bias":
            lo, hi, floor = cfg.dt_init
            dt = jnp.exp(jax.random.uniform(next(keys), shape, F32, jnp.log(lo), jnp.log(hi)))
            dt = jnp.maximum(dt, floor)
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus of it is dt
        if init == "ones_f32":
            return jnp.ones(shape, F32)
        if isinstance(init, float):
            return jnp.full(shape, init, dtype)
        return _normal(next(keys), float(init) ** -0.5, shape, dtype)

    def layer(kind):
        return {n: leaf(n, s, i) for n, (s, i) in _layer_shapes(cfg, *kind).items()}

    params = {
        "layers": tuple(layer(kind) for kind in cfg.layer_kinds),
        "embed": _normal(next(keys), 1.0, (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(
            next(keys), cfg.d_model**-0.5, (cfg.d_model, cfg.vocab_size), dtype
        )
    if cfg.mtp_layers:
        # A key stream of its own: the stack's parameters are the same
        # with the module held and without.
        D = cfg.d_model
        keys = iter(jax.random.split(jax.random.fold_in(key, 0x6D7470), 40))
        params["mtp"] = {
            "enorm": jnp.ones((D,), dtype),
            "hnorm": jnp.ones((D,), dtype),
            # [embedding of the next token ; the stack's output] -> D
            "eh_proj": _normal(next(keys), (2 * D) ** -0.5, (2 * D, D), dtype),
            "layer": layer(cfg.mtp_kind),
            "final_norm": jnp.ones((D,), dtype),
        }
    return params


def balance_router_biases(params: Params, cfg: HybridConfig, key: jax.Array) -> Params:
    """Give every expert layer the selection bias that evens its experts'
    load on what that layer really sees: random prompts of 256 tokens
    (a prefill chunk), one for every eight router outputs (64 at 512: about
    250 choices an expert), go through the model layer by layer, and each
    expert layer's bias is balanced on its own inputs
    (``ops.moe.balanced_bias``) before they go on through it.  Random
    weights stand in for a checkpoint whose
    ``moe_router_enable_expert_bias`` training has done this.

    The sample goes through a layer whole where the expert layer's combine
    of it, (tokens, choices, width) in float32, stays under
    ``BALANCE_WHOLE_BYTES`` (every family before ``longcat_flash``: 0.8-1.9
    GB), and else in equal groups of rows of at most a quarter of that
    (LongCat-Flash's 96 rows of 12 choices of 6,144 would be 7.2 GB beside
    10.35 GB of weights: 16 groups of 6 rows); a layer's scores are those
    of the whole sample either way."""
    if not cfg.router_bias or not any(mlp in EXPERT_MLPS for _, mlp in cfg.layer_kinds):
        return params
    rows = max(4, cfg.router_outputs // 8)
    a_row = 256 * cfg.n_experts_per_tok * (cfg.moe_latent or cfg.d_model) * 4
    groups = 1
    if rows * a_row > BALANCE_WHOLE_BYTES:
        fit = max(1, BALANCE_WHOLE_BYTES // 4 // a_row)
        groups = next(g for g in range(1, rows + 1) if rows % g == 0 and rows // g <= fit)
    biases = iter(_balanced_biases(
        params, cfg, jax.random.randint(key, (rows, 256), 0, cfg.vocab_size, jnp.int32), groups
    ))
    layers = tuple(
        {**lp, "router_bias": next(biases)} if "router_bias" in lp else lp
        for lp in params["layers"]
    )
    params = {**params, "layers": layers}
    if cfg.mtp_layers:
        mtp = params["mtp"]
        params["mtp"] = {**mtp, "layer": {**mtp["layer"], "router_bias": next(biases)}}
    return params


# ``balance_router_biases``: the largest float32 combine a sample goes
# through an expert layer whole with.
BALANCE_WHOLE_BYTES = 2 << 30


def _in_groups(fn, groups: int, *rows):
    """``fn(*rows)`` over the leading (row) axis of every operand (arrays,
    dicts of them, or None): all rows at once, or, ``groups`` > 1, in that
    many equal parts one after the other (a function of each row alone:
    the same numbers, one part's temporaries in memory)."""
    if groups == 1:
        return fn(*rows)
    part = lambda a: a.reshape((groups, a.shape[0] // groups) + a.shape[1:])
    out = jax.lax.map(lambda parts: fn(*parts), jax.tree.map(part, rows))
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("cfg", "groups"))
def _balanced_biases(params, cfg: HybridConfig, tokens, groups: int = 1):
    """``forward`` over whole rows from nothing, with each expert layer's
    bias balanced on that layer's inputs before they pass through it; a
    prediction module's last, on what the stack hands it (the token that
    follows each position is the row's next; the last position's wraps,
    which random tokens do not notice).  ``groups``: the rows go through a
    layer's halves in that many parts (``balance_router_biases``)."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid, n_valid = jnp.ones((b, s), bool), jnp.full((b,), s, jnp.int32)
    state = init_state(cfg, b, s)
    x = params["embed"][tokens]
    out = []

    def through(x, lp, st, mixer, mlp, rho=None, pending=None):
        x = _in_groups(
            lambda x, st, pos, valid, n_valid: _mix(x, lp, st, mixer, pos, valid, n_valid, cfg, s)[0],
            groups, x, st, pos, valid, n_valid,
        )
        if mlp in EXPERT_MLPS:
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            h = h.reshape(-1, h.shape[-1])
            if cfg.router_hidden:
                prev = None if rho is None else rho.reshape(h.shape[0], -1)
                scores, _ = moe.mlp_scores(h, lp, prev, eps=cfg.norm_eps)
            else:
                scores = moe.scores(h, lp["router"], cfg.score_function)
            bias = moe.balanced_bias(
                scores, k=cfg.n_experts_per_tok, n_group=cfg.n_group,
                topk_group=cfg.topk_group,
            )
            out.append(bias)
            lp = {**lp, "router_bias": bias}

        def mlp_half(x, valid, rho, pending):
            x, _, rho, pending = _mlp(x, lp, mlp, valid, cfg, None, rho, pending)
            return x, rho, pending

        return _in_groups(mlp_half, groups, x, valid, rho, pending)

    rho = pending = None
    for (mixer, mlp), lp, st in zip(cfg.layer_kinds, params["layers"], state):
        x, rho, pending = through(x, lp, st, mixer, mlp, rho, pending)
    if cfg.mtp_layers:
        u = _mtp_input(params, cfg, x, jnp.roll(tokens, -1, axis=1))
        through(u, params["mtp"]["layer"], state[cfg.n_layers], *cfg.mtp_kind)
    return out


# -- state ----------------------------------------------------------------------


def init_state(cfg: HybridConfig, batch: int, max_len: int) -> tuple:
    """Zero state for ``batch`` rows: one dict a layer, of its mixer's
    kind; behind them a prediction module's rows (its block's kind: K/V
    rows, or a latent row and an index key a position) and ``h_last``."""
    H, K = cfg.n_heads, cfg.kda_head_dim
    sd = cfg.state_dtype
    out = []
    # A prediction module's block keeps the rows of its mixer's kind.
    module = ((cfg.mtp_kind[0],) if cfg.mtp_layers else ())
    for mixer in tuple(m for m, _ in cfg.layer_kinds) + module:
        if mixer in ("full", "window"):
            rows = max_len if mixer == "full" else cfg.ring_rows(max_len)
            shape = (batch, rows, cfg.n_kv_heads * cfg.attn_head_dim)
            out.append({n: jnp.zeros(shape, sd) for n in GQA_LEAVES[mixer]})
        elif mixer == "cca":
            hd, KH, C = cfg.attn_head_dim, cfg.n_kv_heads, cfg.cca_channels
            rows = (batch, max_len, KH * hd)
            out.append(
                {
                    "k": jnp.zeros(rows, sd), "v": jnp.zeros(rows, sd),
                    "conv0": jnp.zeros((batch, cfg.conv_time0 - 1, C), sd),
                    "conv1": jnp.zeros((batch, cfg.conv_time1 - 1, C), sd),
                    "v_prev": jnp.zeros((batch, 1, KH // 2 * hd), sd),
                }
            )
        elif mixer == "mamba":
            out.append(
                {
                    "ssm": jnp.zeros(
                        (batch, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state), F32
                    ),
                    "conv": jnp.zeros(
                        (batch, cfg.conv_kernel - 1, cfg.mamba_conv_channels), sd
                    ),
                }
            )
        elif mixer == "kda":
            out.append(
                {
                    "S": jnp.zeros((batch, H, K, K), F32),
                    "conv": jnp.zeros(
                        (batch, cfg.conv_kernel - 1, cfg.conv_channels), sd
                    ),
                }
            )
        elif mixer == "mla_window":
            ring = (batch, cfg.ring_rows(max_len), cfg.row_width(mixer))
            out.append({"ring_latent": jnp.zeros(ring, sd)})
        else:
            layer = {"latent": jnp.zeros((batch, max_len, cfg.latent_width), sd)}
            if cfg.index_topk:
                layer["index_k"] = jnp.zeros((batch, max_len, cfg.index_head_dim), sd)
            out.append(layer)
    if cfg.mtp_layers:
        out.append({"h_last": jnp.zeros((batch, cfg.d_model), jnp.dtype(cfg.dtype))})
    return tuple(out)


def state_bytes(cfg: HybridConfig, batch: int, max_len: int) -> dict[str, int]:
    """Bytes of the slots' state by kind, leaf by leaf: ``full`` (rows
    that grow with the tokens: latent, index keys, K/V), ``window`` (rings,
    of K/V or of latent rows: the same at any ``max_len`` over the window), ``recurrent`` (what exists only as
    of the last token: a KDA or ``mamba`` layer's state and tail, a ``cca``
    layer's tails) and,
    where a prediction module is held, ``draft`` (its rows and
    ``h_last``)."""
    out = {"full": 0, "window": 0, "recurrent": 0}
    if cfg.mtp_layers:
        out["draft"] = 0
    shapes = jax.eval_shape(lambda: init_state(cfg, batch, max_len))
    for i, layer in enumerate(shapes):
        for name, leaf in layer.items():
            if i >= cfg.n_layers:
                kind = "draft"
            elif name in ROW_LEAVES:
                kind = "full"
            else:
                kind = "window" if name in RING_LEAVES else "recurrent"
            out[kind] += leaf.size * leaf.dtype.itemsize
    return out


# -- layers ---------------------------------------------------------------------


def _attend(attend, n_valid, apart: bool, *rows):
    """``attend(*rows)`` over the leading (row) axis of every operand: all
    rows in one product, or, ``apart``, a row at a time.  XLA materialises
    a layer's float32 scores (heads x queries x window: 268 MB a row for
    32 heads, a chunk of 256 and 8,192 rows), so the chunks of several
    slots in one program attend one after the other; a row with nothing
    that counts (a group's padding) is passed over.  For K/V rows this is
    what ``ops/gqa_decode.py``'s chunk kernel leaves: float32 state, the
    CPU, several devices."""
    if not apart:
        return attend(*rows)
    shape = jax.eval_shape(attend, *rows)

    def one(args):
        n, *row = args
        return jax.lax.cond(
            n > 0,
            lambda: attend(*(r[None] for r in row))[0],
            lambda: jnp.zeros(shape.shape[1:], shape.dtype),
        )

    return jax.lax.map(one, (n_valid, *rows))


def _kda_mixer(h, lp, st, valid, n_valid, cfg: HybridConfig, mesh=None):
    """A ``kda`` layer.  Returns (output, state, ``STATE_COUNTERS``: from a
    decode step the slots whose state it read and the slots there are;
    from a prefill call the tokens that count whose scan was the chunk
    kernel's (``kda.kda_chunk_rows``) and the tokens that count)."""
    b, s, _ = h.shape
    H, K = cfg.n_heads, cfg.kda_head_dim
    with jax.named_scope("layer/kda/proj"):
        qkv = jnp.dot(h, lp["w_qkv"])
        f = jnp.dot(h, lp["w_f"])
        out_gate = jnp.dot(h, lp["w_g"])
        write = jnp.dot(h, lp["w_b"], preferred_element_type=F32)
    with jax.named_scope("layer/kda/conv"):
        y, xin = kda.causal_conv(qkv, st["conv"], lp["conv_w"])
        tail = kda.next_tail(xin, n_valid, cfg.conv_kernel)
        q, k, v = jnp.split(jax.nn.silu(y).reshape(b, s, 3 * H, K), 3, axis=2)
        q = kda.l2_normalize(q) * K**-0.5
        k = kda.l2_normalize(k)
    with jax.named_scope("layer/kda/gate"):
        on = valid[:, :, None].astype(F32)
        g = kda.kda_gate(
            f.reshape(b, s, H, K), lp["a_log"], lp["dt_bias"], cfg.kda_gate_floor
        ) * on[..., None]
        beta = jax.nn.sigmoid(write) * on
    if s == 1:
        step = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], st["S"])
        if record(
            f"kda_step b={b} h={H}",
            kda.use_step_kernel(state_dtype=st["S"].dtype, k_dim=K, v_dim=K, heads=H, mesh=mesh),
        ):
            o, S = kda.kda_step_rows(*step, valid[:, 0])
            read = (jnp.sum(n_valid), b)
        else:
            o, S = kda.kda_step(*step)
            read = (b, b)
        o = o[:, None]
    else:
        kernel = record(
            f"kda_chunk b={b} s={s} h={H}",
            kda.use_chunk_kernel(state_dtype=st["S"].dtype, k_dim=K, v_dim=K, heads=H, s=s, mesh=mesh),
        )
        if kernel:
            o, S = kda.kda_chunk_rows(q, k, v, g, beta, st["S"], n_valid)
        else:
            o, S = kda.kda_chunked(q, k, v, g, beta, st["S"])
        counted = jnp.sum(n_valid)
        read = (counted if kernel else 0, counted)
    with jax.named_scope("layer/kda/out"):
        o = rms_norm(o, lp["o_norm"].astype(F32), cfg.norm_eps)
        o = o * jax.nn.sigmoid(out_gate.astype(F32)).reshape(b, s, H, K)
        out = jnp.dot(o.reshape(b, s, H * K).astype(h.dtype), lp["w_o"])
    state = {"S": S, "conv": tail.astype(st["conv"].dtype)}
    return out, state, jnp.stack(read).astype(jnp.int32)


def _rescaled(c, cfg: HybridConfig, rank: int):
    """A normed latent times ``(d_model / rank)^1/2`` where the
    configuration says so (``latent_rescale``)."""
    if not cfg.latent_rescale:
        return c
    return (c.astype(F32) * (cfg.d_model / rank) ** 0.5).astype(c.dtype)


def _latent_q_kv(
    h, lp, pos, sz: LatentSizes, cfg: HybridConfig, width: int, *, q_scope: str, kv_scope: str,
    spec: RopeSpec | None,
):
    """What the two latent kinds share: the queries (a low-rank pair or
    one matrix) and the token's row as it is stored; ``spec`` the rotation
    of the rope parts (``None``: the plain frequencies of the kind's
    ``rope_theta``).  Returns (q_nope,
    q_rope rotated and scaled by position, the query's latent or None,
    the new rows (b, s, width): normed latent, rotated rope key, zero
    columns up to ``width``)."""
    b, s, _ = h.shape
    H, rank = sz.n_heads, sz.kv_lora_rank
    nope, rope = sz.qk_nope_head_dim, sz.qk_rope_head_dim
    c_q = None
    with jax.named_scope(q_scope):
        if sz.q_lora_rank:
            c_q = rms_norm(jnp.dot(h, lp["w_qa"]), lp["q_norm"], cfg.norm_eps)
            c_q = _rescaled(c_q, cfg, sz.q_lora_rank)
            q = jnp.dot(c_q, lp["w_qb"]).reshape(b, s, H, nope + rope)
        else:
            q = jnp.dot(h, lp["w_q"]).reshape(b, s, H, nope + rope)
        q_nope = q[..., :nope]
        q_rope = mla.rope_interleaved(q[..., nope:], pos, sz.rope_theta, spec)
        if cfg.attn_scale_beta:
            a = mla.position_scale(pos, cfg.attn_scale_beta, spec.original_max)
            a = a[:, :, None, None]
            q_nope = (q_nope.astype(F32) * a).astype(q.dtype)
            q_rope = (q_rope.astype(F32) * a).astype(q.dtype)
    with jax.named_scope(kv_scope):
        ckr = jnp.dot(h, lp["w_kva"])
        c = _rescaled(rms_norm(ckr[..., :rank], lp["kv_norm"], cfg.norm_eps), cfg, rank)
        k_rope = mla.rope_interleaved(ckr[..., rank:], pos, sz.rope_theta, spec)
        spare = jnp.zeros((b, s, width - rank - rope), c.dtype)  # none in Ling's rows
        new = jnp.concatenate([c, k_rope, spare], axis=-1)
    return q_nope, q_rope, c_q, new


def _gated_out(o, h, lp, cfg: HybridConfig, scope: str):
    """The heads' outputs (b, s, H, v), each times its sigmoid gate where
    the configuration has one, through ``W_o``."""
    b, s, H, vd = o.shape
    with jax.named_scope(f"{scope}/wo"):
        if cfg.mla_out_gate:
            gate = jax.nn.sigmoid(jnp.dot(h, lp["w_gate"], preferred_element_type=F32))
            o = (o.astype(F32) * gate[..., None]).astype(h.dtype)
        return jnp.dot(o.reshape(b, s, H * vd), lp["w_o"])


# The LayerNorm of an index key (DeepSeek-V3.2's public inference code).
INDEX_NORM_EPS = 1e-6


def _index_q_k(h, c_q, lp, pos, cfg: HybridConfig):
    """The indexer's projections of an ``mla`` layer: (q_I (b, s, HI, d),
    w (b, s, HI) float32, k_I (b, s, d)), the first ``qk_rope_head_dim``
    values of every query and key rotated as the main rope part is."""
    b, s, _ = h.shape
    HI, dI, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim

    def rotated(x):
        turned = mla.rope_interleaved(x[..., :rope], pos, cfg.rope_theta, cfg.rope_latent)
        return jnp.concatenate([turned, x[..., rope:]], axis=-1)

    with jax.named_scope("layer/mla/index"):
        q_i = rotated(jnp.dot(c_q, lp["w_qi"]).reshape(b, s, HI, dI))
        k = jnp.dot(h, lp["w_ki"]).astype(F32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + INDEX_NORM_EPS)
        k = (k * lp["ki_norm"].astype(F32) + lp["ki_norm_b"].astype(F32)).astype(h.dtype)
        w = jnp.dot(h, lp["w_wi"], preferred_element_type=F32) * (HI**-0.5 * dI**-0.5)
    return q_i, w, rotated(k)


def _mla_mixer(
    h, lp, st, pos, valid, n_valid, cfg: HybridConfig, window: int, apart: bool,
    site: str = "", mesh=None,
):
    """An ``mla`` layer, Ling's form, the mistral4 family's or the
    dots3_note family's by what the configuration says (a low-rank query,
    YaRN on the rotary part, a query scale by position, no output gate,
    prefill in blocks; the normed latents rescaled, attention over the
    rows an indexer selects).  Returns (output, state, the rows read by
    name: ``LATENT_COUNTERS``, and ``INDEX_COUNTERS`` with an indexer).

    ``st`` may carry ``slot`` (b,) beside ``latent``: the latent rows are
    then a state of many slots, of which row ``i`` of this call is slot
    ``slot[i]`` (absent: slot ``i``); the new rows are written there and
    the block forms read a row's blocks from there, a row at a time, so
    that no window of it is ever copied (a chunk program of a model whose
    state is rows alone: ``HybridServing.prefill_rows``).

    With an indexer (``cfg.index_topk``) a slot keeps one index key a
    position beside its latent row (``index_k``).  A prefill call scores,
    for each row that ends up longer than ``index_topk``, every position
    up to its length a block at a time (``mla.index_scores_blocks``),
    keeps each query's ``index_topk`` highest (``mla.select_mask``) and
    hands the kept pairs to the block walk, which still expands and scores
    every block up to the row's length; a shorter row keeps every position
    it sees and is attended as without an indexer.  The walk of a prefill
    chunk, selected or not, is ``ops/mla_chunk.py``'s kernel for all rows
    of the call at once where ``use_latent_chunk`` admits it (the counter
    ``kernel_latent``: the rows it walked), and ``mla.attend_blocks`` a row
    at a time where it does not.  A decode step without an indexer walks
    each decoding row's blocks up to its length in ``ops/mla_decode.py``'s
    kernel, every row of the step in one call, where ``use_latent_decode``
    admits it (``kernel_latent`` again), and ``mla.attend_absorbed_blocks``
    a row at a time where it does not.  With an indexer a step
    (``gqa._STEP_QUERIES`` queries a
    slot or fewer: a decode step's one, a verify step's ``[token, draft]``,
    a prediction module's block beside either) never takes the chunk's
    form: it scores every slot's first ``window`` index keys against the
    step's queries in one product, gathers for EACH query the
    ``index_topk`` highest rows of what it sees (``mla.select_rows``:
    position ``p + 1`` selects among ``j <= p + 1``, its own new row among
    them, and its set is not position ``p``'s) and attends over them alone
    (``mla.attend_selected``); a slot that does not decode is computed
    beside the others and its result dropped.  ``site`` prefixes the
    layer's ``kernel_paths`` entries (a prediction module's block)."""
    b, s, _ = h.shape
    sz = cfg.latent_sizes("mla")
    H, rank = sz.n_heads, sz.kv_lora_rank
    nope, rope, vd = sz.qk_nope_head_dim, sz.qk_rope_head_dim, sz.v_head_dim
    T, width = st["latent"].shape[1:]
    q_nope, q_rope, c_q, new = _latent_q_kv(
        h, lp, pos, sz, cfg, width, kv_scope="layer/mla/kv", spec=cfg.rope_latent,
        q_scope="layer/mla/q_lora" if sz.q_lora_rank else "layer/mla/q",
    )
    # A token that does not count is written nowhere.
    at = jnp.where(valid, pos, T)
    slot = st.get("slot")
    mine = jnp.arange(b) if slot is None else slot
    with jax.named_scope("layer/mla/kv"):
        latent = st["latent"].at[mine[:, None], at].set(
            new.astype(st["latent"].dtype), mode="drop"
        )
    new_state = {"latent": latent}
    topk = cfg.index_topk
    if topk:
        q_i, w_i, k_i = _index_q_k(h, c_q, lp, pos, cfg)
        with jax.named_scope("layer/mla/index"):
            index_k = st["index_k"].at[mine[:, None], at].set(
                k_i.astype(st["index_k"].dtype), mode="drop"
            )
        new_state["index_k"] = index_k
    if slot is not None:
        new_state["slot"] = slot
    span = min(window, T)
    sizes = dict(w_kvb=lp["w_kvb"], rank=rank, nope=nope, v_dim=vd)
    if cfg.softmax_mscale != 1.0:
        sizes["scale"] = (nope + rope) ** -0.5 * cfg.softmax_mscale**2
    read = {"read_latent": b * span, "dense_latent": b * span}

    def in_place(attend, block, lengths, *more):
        """A row at a time, its whole blocks up to ``lengths`` read from
        its slot of the state in place; a row that holds nothing (a
        group's padding, a slot that does not decode) is passed over.
        ``more``: further operands a row of the batch each, handed to
        ``attend`` after the first five."""

        def one(row):
            qn, qr, sl, p, n, *rest = (x[None] for x in row)
            return jax.lax.cond(
                n[0] > 0,
                lambda: attend(
                    qn, qr, latent, *rest, q_pos=p, lengths=n, block=block, slot=sl,
                    window=span, **sizes
                )[0],
                lambda: jnp.zeros((s, H, vd), q_nope.dtype),
            )

        return jax.lax.map(one, (q_nope, q_rope, mine, pos, lengths, *more))

    def chunk_kernel(name: str, masked: bool) -> bool:
        return record(site + name, mla_chunk.use_latent_chunk(
            s=s, q_dtype=q_nope.dtype, rows_dtype=latent.dtype, width=width, rank=rank,
            nope=nope, v_dim=vd, heads=H, rows=T, window=span, block=cfg.latent_block,
            masked=masked, mesh=mesh,
        ))

    def chunk(lengths, **selection):
        return mla_chunk.attend_latent_chunk(
            q_nope, q_rope, latent, q_pos=pos, lengths=lengths, slot=mine, window=span,
            block=cfg.latent_block, **selection, **sizes
        )

    if topk and s > gqa._STEP_QUERIES:
        record(f"{site}index_scores b={b} s={s} t={span}", False)
        kernel = chunk_kernel(f"attn_latent_chunk b={b} s={s} t={span} k={topk}", True)
        # Rows each row holds once its tokens are written: one past its
        # last position that counts (a prediction module's block runs one
        # position behind, and a prompt's position -1 does not count).
        lengths = jnp.max(jnp.where(valid, pos + 1, 0), axis=1)
        selects = lengths > topk  # a shorter row keeps every position it sees
        walked = mla.rows_in_blocks(lengths, span, cfg.latent_block)
        scored = jnp.where(selects, walked, 0)
        # Pairs a query of a row that counts sees, and those the walk
        # scores for it: every row of every block walked, kept or not.
        seen = jnp.where(valid, jnp.minimum(pos + 1, span), 0)
        read.update(
            read_latent=walked.sum(),
            index_pairs=(scored * n_valid).sum(),
            read_index=scored.sum(),
            read_selected=(walked * n_valid).sum(),
            seen_latent=seen.sum(),
        )

        def kept(qi, wi, q_pos, lengths, slot):
            """The pairs the indexer keeps of one row, (1, s, span) bool."""
            scores = mla.index_scores_blocks(
                qi, wi, index_k, q_pos, lengths, block=cfg.latent_block, slot=slot, window=span
            )
            return mla.select_mask(scores, topk)

        def attend(qn, qr, lat, qi, wi, *, q_pos, lengths, slot, **kw):
            walk = functools.partial(
                mla.attend_blocks, qn, qr, lat, q_pos=q_pos, lengths=lengths, slot=slot, **kw
            )
            return jax.lax.cond(
                lengths[0] > topk,
                lambda: walk(allowed=kept(qi, wi, q_pos, lengths, slot)),
                walk,
            )

        def mask(row):
            """A row's selection as the kernel's mask; that of a row that
            does not select is not read."""
            qi, wi, p, n, sl = (x[None] for x in row)
            return jax.lax.cond(
                n[0] > topk,
                lambda: kept(qi, wi, p, n, sl)[0].astype(jnp.int8),
                lambda: jnp.zeros((s, span), jnp.int8),
            )

        if kernel:
            read["kernel_latent"] = walked.sum()
            allowed = jax.lax.map(mask, (q_i, w_i, pos, lengths, mine))
            o = chunk(lengths, allowed=allowed, selects=selects)
        else:
            o = in_place(attend, cfg.latent_block, lengths, q_i, w_i)
    elif topk:
        form = "decode" if s == 1 else "verify"
        record(f"{site}index_scores b={b} s={s} t={span}", False)
        record(f"{site}attn_latent_sparse_{form} b={b} t={span} k={min(topk, span)}", False)
        keys, rows = (index_k, latent) if slot is None else (index_k[mine], latent[mine])
        with jax.named_scope("layer/mla/index"):
            scores = mla.index_scores(q_i, w_i, jax.lax.slice_in_dim(keys, 0, span, axis=1))
            seen = jnp.arange(span, dtype=jnp.int32)[None, None, :] <= pos[:, :, None]
            scores = jnp.where(seen & valid[:, :, None], scores, -jnp.inf)
        # One row of scores a slot and position: each position selects for itself.
        idx, keep = mla.select_rows(scores.reshape(b * s, span), topk)
        idx, keep = idx.reshape(b, s, -1), keep.reshape(b, s, -1)
        # Every slot's index keys are read and every slot's rows gathered
        # for every position, whoever decodes: what the step read, not what
        # it needed (``needed_verify``: the decoding slots' sets, united).
        gathered = b * s * idx.shape[-1]
        read.update(
            read_latent=gathered, index_pairs=b * s * span, read_index=b * span,
            read_selected=gathered,
            seen_latent=jnp.where(valid, jnp.minimum(pos + 1, span), 0).sum(),
            gathered_verify=gathered,
            needed_verify=mla.rows_needed(scores, idx, keep, valid).sum(),
        )
        o = mla.attend_selected(q_nope, q_rope, rows, idx=idx, keep=keep, **sizes)
    elif cfg.latent_block and s > 1:
        kernel = chunk_kernel(f"attn_latent_chunk b={b} s={s} t={span}", False)
        # Rows each row holds once its tokens are written; a row with
        # nothing that counts reads nothing.
        lengths = jnp.where(n_valid > 0, pos[:, 0] + n_valid, 0)
        read["read_latent"] = mla.rows_in_blocks(lengths, span, cfg.latent_block).sum()
        if kernel:
            read["kernel_latent"] = read["read_latent"]
            o = chunk(lengths)
        else:
            o = in_place(mla.attend_blocks, cfg.latent_block, lengths)
    elif cfg.latent_block:
        kernel = record(f"attn_latent_decode b={b} t={span}", mla_decode.use_latent_decode(
            s=s, q_dtype=q_nope.dtype, rows_dtype=latent.dtype, width=width, rank=rank, heads=H,
            rows=T, window=span, block=cfg.latent_decode_block, mesh=mesh,
        ))
        # A decode step: a row that does not decode reads nothing.
        lengths = jnp.where(n_valid > 0, pos[:, 0] + 1, 0)
        read["read_latent"] = mla.rows_in_blocks(lengths, span, cfg.latent_decode_block).sum()
        if kernel:
            read["kernel_latent"] = read["read_latent"]
            o = mla_decode.attend_latent_decode(
                q_nope, q_rope, latent, q_pos=pos, lengths=lengths, slot=mine, window=span,
                block=cfg.latent_decode_block, **sizes
            )
        else:
            o = in_place(mla.attend_absorbed_blocks, cfg.latent_decode_block, lengths)
    else:
        attend = functools.partial(
            mla.attend_absorbed if s == 1 else mla.attend_expanded, **sizes
        )
        o = _attend(
            lambda qn, qr, lat, p: attend(qn, qr, lat, q_pos=p),
            n_valid, apart, q_nope, q_rope, latent[:, :window], pos,
        )
    return _gated_out(o, h, lp, cfg, "layer/mla"), new_state, read


def _mla_window_mixer(h, lp, st, pos, valid, n_valid, cfg: HybridConfig, window: int, apart: bool):
    """An ``mla_window`` layer: latent attention of the kind's own sizes
    (``cfg.window_latent``) over the last ``sliding_window`` positions.
    The state is a ring of latent rows (``ring_latent``: position ``p`` in
    row ``p % R``); a call attends over the ring as it was and over its
    own rows, in one softmax (``mla.attend_latent_ring``), then writes.
    Returns (output, state, counters by name: ``RING_LATENT_COUNTERS``,
    the ring's rows against the ``window`` rows a full layer beside it
    may see)."""
    b, s, _ = h.shape
    sz = cfg.latent_sizes("mla_window")
    ring = st["ring_latent"]
    rows = ring.shape[1]
    q_nope, q_rope, _, new = _latent_q_kv(
        h, lp, pos, sz, cfg, ring.shape[2], q_scope="layer/mla_window/q",
        kv_scope="layer/mla_window/kv", spec=None,
    )
    record(f"attn_latent_ring b={b} s={s} t={rows}", False)
    with jax.named_scope("layer/mla_window/attn"):
        attend = functools.partial(
            mla.attend_latent_ring, w_kvb=lp["w_kvb"], rank=sz.kv_lora_rank,
            nope=sz.qk_nope_head_dim, v_dim=sz.v_head_dim, window=cfg.sliding_window,
        )
        # A chunk's float32 scores a row at a time; a decode step all slots at once.
        o = _attend(
            lambda qn, qr, nw, rg, p: attend(qn, qr, nw, rg, q_pos=p),
            n_valid, apart and s > gqa._STEP_QUERIES, q_nope, q_rope, new, ring, pos,
        )
    with jax.named_scope("layer/mla_window/kv"):
        at = gqa.ring_slots(pos, valid, n_valid, rows)
        ring = ring.at[jnp.arange(b)[:, None], at].set(new.astype(ring.dtype), mode="drop")
    # A chunk's row with nothing that counts is passed over (``_attend``).
    live = jnp.sum(n_valid > 0) if apart and s > gqa._STEP_QUERIES else b
    read = {"read_window": live * rows, "dense_window": b * window}
    return _gated_out(o, h, lp, cfg, "layer/mla_window"), {"ring_latent": ring}, read


def _gqa_mixer(
    h, lp, st, mixer, pos, valid, n_valid, cfg: HybridConfig, window: int, apart: bool,
    site: str = "", mesh=None,
):
    """A ``full`` or ``window`` layer.  Returns (output, state, counters
    in the order of ``ATTN_COUNTERS``): a full layer writes its rows and
    attends over the first ``window`` of them (a decode step walks the
    rows each slot holds, where ``gqa_decode.use_row_walk`` admits it);
    a window layer attends over its ring as it was and over its own new
    rows (a prefill chunk in ``ops/gqa_decode.py``'s ring kernel, where
    ``use_ring_chunk`` admits it; a decode step and what it refuses in
    ``gqa.attend_ring``), then writes.  ``site`` prefixes the layer's
    ``kernel_paths`` entry (a prediction module's block)."""
    b, s, _ = h.shape
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.attn_head_dim
    scope = f"layer/attn_{mixer}"
    spec = cfg.rope_full if mixer == "full" else cfg.rope_window
    with jax.named_scope(f"{scope}/qkv"):
        qkv = jnp.dot(h, lp["w_qkv"]).reshape(b, s, H + 2 * KH, hd)
        q, k, v = qkv[:, :, :H], qkv[:, :, H : H + KH], qkv[:, :, H + KH :]
    if cfg.qk_norm:
        with jax.named_scope(f"{scope}/qk_norm"):
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    with jax.named_scope(f"{scope}/rope"):
        q, k = apply_rope_spec(q, pos, spec), apply_rope_spec(k, pos, spec)
        k, v = k.reshape(b, s, KH * hd), v.reshape(b, s, KH * hd)  # a state row
    names = GQA_LEAVES[mixer]
    old_k, old_v = (st[n] for n in names)
    if mixer == "full":
        o, new_k, new_v, read = _full_rows(
            q, k, v, old_k, old_v, pos, valid, n_valid, n_kv=KH, window=window,
            apart=apart, scope=scope, site=f"{site}attn_full", mesh=mesh,
            slot=st.get("slot"),
        )
    else:
        rows = old_k.shape[1]
        if s > gqa._STEP_QUERIES:
            chunk = record(
                f"{site}attn_window_chunk b={b} s={s} t={rows}",
                gqa_decode.use_ring_chunk(
                    s=s, q_dtype=q.dtype, rows_dtype=old_k.dtype, width=KH * hd, head_dim=hd,
                    ring=rows, n_q=H, mesh=mesh,
                ),
            )
        else:
            chunk = record(f"{site}attn_window b={b} s={s} t={rows}", False)
        ring = dict(n_kv=KH, window=cfg.sliding_window)
        with jax.named_scope(f"{scope}/attend"):
            if chunk:
                o = gqa_decode.attend_ring_chunk(q, k, v, old_k, old_v, pos, n_valid, **ring)
            else:
                o = gqa.attend_ring(q, k, v, old_k, old_v, pos, **ring)
        new_k, new_v = _write_rows(
            old_k, old_v, k, v, gqa.ring_slots(pos, valid, n_valid, rows), scope
        )
        # The kernel reads no ring for a row with nothing that counts.
        read = ((jnp.sum(n_valid > 0) if chunk else b) * rows, 0, b * window, 0)
    with jax.named_scope(f"{scope}/wo"):
        out = jnp.dot(o.reshape(b, s, H * hd).astype(h.dtype), lp["w_o"])
    return out, dict(zip(names, (new_k, new_v))), jnp.stack(read).astype(jnp.int32)


def _write_rows(old_k, old_v, k, v, at, scope: str, slot=None):
    """The call's K and V rows (b, s, KH * D) into the slots' rows at
    ``at`` (b, s), row ``i`` into slot ``slot[i]`` (absent: slot ``i``); a
    token that does not count is written nowhere (its ``at`` is the number
    of rows: dropped)."""
    with jax.named_scope(f"{scope}/kv_write"):
        slots = (jnp.arange(k.shape[0]) if slot is None else slot)[:, None]
        return (
            old_k.at[slots, at].set(k.astype(old_k.dtype), mode="drop"),
            old_v.at[slots, at].set(v.astype(old_v.dtype), mode="drop"),
        )


def _full_rows(
    q, k, v, old_k, old_v, pos, valid, n_valid, *, n_kv: int, window: int, apart: bool,
    scope: str, site: str, mesh, slot=None,
):
    """Attention over rows a position, which the ``full`` and the ``cca``
    kinds share: the call's rows are written, then a query attends over
    the first ``window`` of its slot's rows.  Where ``ops/gqa_decode.py``'s
    gates admit it, a decode step walks the rows each slot holds
    (``use_row_walk``) and a prefill chunk the rows its slot holds up to
    its own last position, where they lie (``use_row_chunk``); what they
    refuse is ``gqa.attend_rows`` over the whole window.

    q: (b, s, H, D) rotated; k, v: (b, s, KH * D).  With ``slot`` (b,) the
    rows are a state of many slots, of which row ``i`` of the call is slot
    ``slot[i]``: its rows are written there, in place, and read from there
    (the chunk kernel), or the call's rows' windows are gathered from there
    for this layer alone (XLA), so that no window is written back and no
    more than one layer's are held (a chunk program:
    ``HybridServing.prefill_rows``).
    Returns (o (b, s, H, D), K rows, V rows, counters in the order of
    ``ATTN_COUNTERS``)."""
    b, s, H, hd = q.shape
    rows = old_k.shape[1]
    span = min(window, rows)  # of the slot's rows, those a call may see
    shape = dict(
        s=s, q_dtype=q.dtype, rows_dtype=old_k.dtype, width=n_kv * hd, head_dim=hd,
        rows=rows, window=span, n_q=H, mesh=mesh,
    )
    chunk = s > gqa._STEP_QUERIES and record(
        f"{site}_chunk b={b} s={s} t={window}", gqa_decode.use_row_chunk(**shape)
    )
    walk = s <= gqa._STEP_QUERIES and record(
        f"{site} b={b} s={s} t={window}",
        gqa_decode.use_row_walk(batch=b, apart=apart or slot is not None, **shape),
    )
    new_k, new_v = _write_rows(old_k, old_v, k, v, jnp.where(valid, pos, rows), scope, slot)
    read_full = b * span
    with jax.named_scope(f"{scope}/attend"):
        if chunk:
            lengths = gqa_decode.chunk_lengths(pos, valid, span)
            o = gqa_decode.attend_rows_chunk(
                q, new_k, new_v, pos, lengths, n_kv=n_kv, window=span, slot=slot
            )
            read_full = gqa_decode.rows_walked(lengths, rows, span)
        elif walk:
            lengths = gqa_decode.walk_lengths(pos, n_valid, span)
            o = gqa_decode.attend_rows_walk(
                q, new_k, new_v, pos, lengths, n_kv=n_kv, window=span
            )
            read_full = gqa_decode.rows_walked(lengths, rows, span)
        elif slot is not None:
            # The call's rows' windows, gathered from where they were just
            # written: one layer's at a time (67 MB for 8 rows of 8,192),
            # not every layer's before the stack runs.
            o = _attend(
                functools.partial(gqa.attend_rows, n_kv=n_kv), n_valid, True,
                q, new_k[slot, :window], new_v[slot, :window], pos,
            )
        else:
            o = _attend(
                functools.partial(gqa.attend_rows, n_kv=n_kv), n_valid, apart,
                q, new_k[:, :window], new_v[:, :window], pos,
            )
    return o, new_k, new_v, (0, read_full, 0, b * span)


def _cca_mixer(
    h, lp, st, pos, valid, n_valid, cfg: HybridConfig, window: int, apart: bool, mesh=None,
):
    """A ``cca`` layer (``ops/cca.py``): q and k latents through the two
    causal convolutions, the mean of the un-mixed q and k added back, each
    head normed to length ``sqrt(d)`` (k times its temperature), the
    rotation over the first ``rotary_dim`` of a head, half of the value
    heads taken from the token before; then a full layer's attention over
    the slot's rows.  Every history (the convolutions' last inputs, the
    last token's late values) is continued from the slot's tails, which
    move only past tokens that count.  ``st`` may carry ``slot`` (b,)
    beside its leaves: ``k`` and ``v`` are then the rows of many slots,
    written in place (``_full_rows``); the tails are the call's rows' own
    either way.  Returns (output, state, counters in the order of
    ``ATTN_COUNTERS``)."""
    b, s, _ = h.shape
    H, KH, hd, C = cfg.n_heads, cfg.n_kv_heads, cfg.attn_head_dim, cfg.cca_channels
    scope = "layer/attn_cca"
    with jax.named_scope(f"{scope}/qk"):  # and the values: one product
        qkv = jnp.dot(h, lp["w_qkv"])
        u, v_now, v_late = jnp.split(qkv, [C, C + KH // 2 * hd], axis=-1)
    with jax.named_scope(f"{scope}/conv"):
        a, xin0 = kda.causal_conv(u, st["conv0"], lp["conv0_w"])
        a = (a + lp["conv0_b"].astype(F32)).astype(h.dtype)
        z, xin1 = cca.head_conv(a, st["conv1"], lp["conv1_w"], lp["conv1_b"])
    with jax.named_scope(f"{scope}/qk_mean"):
        q, k = cca.add_qk_mean(z, u, H)
    with jax.named_scope(f"{scope}/norm_rope"):
        q = kda.l2_normalize(q) * hd**0.5
        k = kda.l2_normalize(k) * (hd**0.5 * lp["k_temp"].astype(F32))[:, None]
        q, k = (
            apply_rope_partial(x.astype(h.dtype), pos, cfg.rope_full, cfg.rotary_dim)
            for x in (q, k)
        )
    with jax.named_scope(f"{scope}/v_shift"):
        before, late = cca.shift(v_late, st["v_prev"])
        v = jnp.concatenate([v_now, before], axis=-1)
    o, new_k, new_v, read = _full_rows(
        q, k.reshape(b, s, KH * hd), v, st["k"], st["v"], pos, valid, n_valid,
        n_kv=KH, window=window, apart=apart, scope=scope, site="attn_cca", mesh=mesh,
        slot=st.get("slot"),
    )
    with jax.named_scope(f"{scope}/wo"):
        out = jnp.dot(o.reshape(b, s, H * hd).astype(h.dtype), lp["w_o"])
    sd = st["conv0"].dtype
    state = {
        "k": new_k, "v": new_v,
        "conv0": kda.next_tail(xin0, n_valid, cfg.conv_time0).astype(sd),
        "conv1": kda.next_tail(xin1, n_valid, cfg.conv_time1).astype(sd),
        "v_prev": kda.next_tail(late, n_valid, 2).astype(sd),
    }
    return out, state, jnp.stack(read).astype(jnp.int32)


def _mamba_mixer(h, lp, st, valid, n_valid, cfg: HybridConfig):
    """A ``mamba`` layer (``ops/ssm.py``): one projection to the gate
    ``z``, what the convolution mixes (``x``, ``B``, ``C``) and the heads'
    steps ``dt``; a depthwise causal convolution with a bias, continued
    from the slot's tail, and a SiLU; the state-space scan from the slot's
    state (a decode step: one update of it); the gated norm a group; the
    output projection.  A token that does not count has ``dt`` 0 and moves
    neither state nor tail.  Returns (output, state, counters by name:
    ``STATE_COUNTERS`` from a decode step, ``SSM_COUNTERS`` from a prefill
    call)."""
    b, s, _ = h.shape
    H, P, G, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups, cfg.ssm_state
    inner = H * P
    with jax.named_scope("layer/mamba/proj"):
        z, u, d = jnp.split(jnp.dot(h, lp["w_in"]), [inner, inner + cfg.mamba_conv_channels], axis=-1)
    with jax.named_scope("layer/mamba/conv"):
        c, xin = kda.causal_conv(u, st["conv"], lp["conv_w"])
        tail = kda.next_tail(xin, n_valid, cfg.conv_kernel)
        c = jax.nn.silu(c + lp["conv_b"].astype(F32)).astype(h.dtype)
        xs, b_in, c_in = jnp.split(c, [inner, inner + G * N], axis=-1)
        xs = xs.reshape(b, s, H, P)
        b_in, c_in = b_in.reshape(b, s, G, N), c_in.reshape(b, s, G, N)
    with jax.named_scope("layer/mamba/dt"):
        dt = jax.nn.softplus(d.astype(F32) + lp["ssm_dt_bias"]) * valid[:, :, None].astype(F32)
        a = -jnp.exp(lp["ssm_a_log"].astype(F32))
    if s == 1:
        record(f"ssm_step b={b} h={H}", False)
        y, S = ssm.ssm_step(xs[:, 0], dt[:, 0], a, b_in[:, 0], c_in[:, 0], lp["ssm_d"], st["ssm"])
        y = y[:, None]
        read = {"read_state": b, "dense_state": b}
    else:
        record(f"ssm_scan b={b} s={s}", False)
        y, S = ssm.ssm_scan(xs, dt, a, b_in, c_in, lp["ssm_d"], st["ssm"], block=cfg.ssm_block)
        read = {"ssm_tokens": jnp.sum(n_valid), "ssm_blocks": b * ssm.blocks_of(s, cfg.ssm_block)[1]}
    with jax.named_scope("layer/mamba/norm"):
        v = ssm.gated_group_norm(y.reshape(b, s, inner), z, lp["ssm_norm"], G, cfg.norm_eps)
    with jax.named_scope("layer/mamba/out"):
        out = jnp.dot(v.astype(h.dtype), lp["w_out"])
    return out, {"ssm": S, "conv": tail.astype(st["conv"].dtype)}, read


def _swiglu(h, w_gu, w_down):
    gu = jnp.dot(h, w_gu)
    half = gu.shape[-1] // 2
    act = jax.nn.silu(gu[..., :half].astype(F32)) * gu[..., half:].astype(F32)
    return jnp.dot(act.astype(h.dtype), w_down)


def _relu2(h, w_up, w_down):
    """``W2 relu(W1 h)^2``: an MLP without a gate."""
    act = jnp.square(jax.nn.relu(jnp.dot(h, w_up).astype(F32)))
    return jnp.dot(act.astype(h.dtype), w_down)


def _expert_layer(h, lp, valid, cfg: HybridConfig, mesh, rho=None):
    """The experts' half of a layer.  ``rho`` is the router state the layer
    before handed on (a ZAYA router's: ``cfg.router_hidden``; None for the
    first layer and for every linear router).  Returns (y, counters, this
    layer's router state or None)."""
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    if cfg.router_hidden:
        prev = None if rho is None else rho.reshape(b * s, -1)
        idx, w, rho = moe.route_mlp(x, lp, prev, eps=cfg.norm_eps)
        rho = rho.reshape(b, s, -1)
    else:
        idx, w = moe.route(
            x, lp["router"], lp.get("router_bias"), k=cfg.n_experts_per_tok,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            norm_topk=cfg.norm_topk, scale=cfg.routed_scaling,
            score=cfg.score_function,
        )
    u = x
    if cfg.moe_latent:
        # The routed experts work in a latent; the router saw the token itself.
        with jax.named_scope("layer/moe/latent/down"):
            u = jnp.dot(x, lp["w_lat_down"])
    y, counters = moe.expert_mlp(
        u, idx, w, valid.reshape(-1), lp,
        offset=cfg.expert_offset, held=cfg.experts_held, mesh=mesh, act=cfg.expert_act,
        zero_from=cfg.n_experts if cfg.zero_experts else None,
    )
    if cfg.moe_latent:
        # Linear: the shares' partial sums, each through its own copy, add up.
        with jax.named_scope("layer/moe/latent/up"):
            y = jnp.dot(y, lp["w_lat_up"])
    if cfg.shared_d_ff:
        with jax.named_scope("layer/moe/shared"):
            if cfg.expert_act == "relu2":
                y = y + _relu2(x, lp["w_up_s"], lp["w_down_s"])
            else:
                y = y + _swiglu(x, lp["w_gu_s"], lp["w_down_s"])
    return y.reshape(b, s, d), counters, rho


def _mix(
    x, lp, st, mixer, pos, valid, n_valid, cfg: HybridConfig, window: int,
    apart: bool = False, site: str = "", mesh=None,
):
    """The mixer's half of a layer: (x + mixer(norm(x)), new state, what
    the layer read, int32, ``cfg.row_counters`` and then
    ``cfg.step_counters``: each mixer's counters go by name to the entries
    the model has, the others nowhere)."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if mixer == "kda":
        y, st, read = _kda_mixer(h, lp, st, valid, n_valid, cfg, mesh)
        names = STATE_COUNTERS
    elif mixer == "mla":
        y, st, named = _mla_mixer(
            h, lp, st, pos, valid, n_valid, cfg, window, apart, site, mesh
        )
        names, read = tuple(named), tuple(named.values())
    elif mixer == "mla_window":
        y, st, named = _mla_window_mixer(h, lp, st, pos, valid, n_valid, cfg, window, apart)
        names, read = tuple(named), tuple(named.values())
    elif mixer == "mamba":
        y, st, named = _mamba_mixer(h, lp, st, valid, n_valid, cfg)
        names, read = tuple(named), tuple(named.values())
    elif mixer == "cca":
        y, st, read = _cca_mixer(h, lp, st, pos, valid, n_valid, cfg, window, apart, mesh)
        names = ATTN_COUNTERS
    else:
        y, st, read = _gqa_mixer(
            h, lp, st, mixer, pos, valid, n_valid, cfg, window, apart, site, mesh
        )
        names = ATTN_COUNTERS
    by_name = dict(zip(names, read))
    wanted = cfg.row_counters + cfg.step_counters
    read = jnp.stack(
        [jnp.asarray(by_name.get(n, 0), jnp.int32) for n in wanted]
    ) if wanted else jnp.zeros((0,), jnp.int32)
    return x + y, st, read


def _mlp(x, lp, mlp, valid, cfg: HybridConfig, mesh, rho=None, pending=None):
    """The MLP's half: (x + mlp(norm(x)), the expert layer's counters, the
    router state to hand the next layer: ``_expert_layer``, the pending
    branch to hand it); ``x`` as it came where the layer is its mixer
    alone.  A ``shortcut`` layer adds its dense MLP and hands its experts'
    sum on as ``pending`` (both read the same normed ``x``); the
    ``dense_add`` layer after it adds its dense MLP and then that sum.
    ``rho`` and ``pending`` are None wherever no layer has either: what a
    program carries is decided from the configuration at trace time."""
    if mlp == "none":
        return x, 0, rho, pending
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    counters = 0
    if mlp in EXPERT_MLPS:
        y, counters, rho = _expert_layer(h, lp, valid, cfg, mesh, rho)
        if mlp == "experts":
            return x + y, counters, rho, pending
        pending = y
    with jax.named_scope("layer/mlp"):
        x = x + _swiglu(h, lp["w_gu"], lp["w_down"])
    if mlp == "dense_add":
        with jax.named_scope("layer/moe/shortcut_add"):
            x, pending = x + pending, None
    return x, counters, rho, pending


def forward(
    params: Params,
    cfg: HybridConfig,
    tokens: jnp.ndarray,
    start: jnp.ndarray,
    n_valid: jnp.ndarray,
    state: tuple,
    *,
    window: int,
    mesh=None,
    rows_apart: bool = False,
):
    """tokens (b, s) at positions ``start[b] + [0, s)``, of which the first
    ``n_valid[b]`` count; ``state`` is these rows' state; MLA layers
    and full layers attend over the first ``window`` rows, ``rows_apart``
    a row at a time (``_attend``: the same numbers, one row's scores in
    memory).  Returns (hidden (b, s, D), state, counters
    (cfg.n_counters,) int32 summed over layers).  The hidden states are
    the stack's output before its final norm."""
    b, s = tokens.shape
    x = params["embed"][tokens]
    steps = jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = start[:, None].astype(jnp.int32) + steps
    valid = steps < n_valid[:, None]
    counters = jnp.zeros((len(moe.COUNTERS),), jnp.int32)
    read = jnp.zeros((len(cfg.row_counters) + len(cfg.step_counters),), jnp.int32)
    out_state = []
    # Beside ``x`` from layer to layer: a ZAYA router's state, and the
    # experts' sum a ``shortcut`` layer started, until its ``dense_add``.
    rho = pending = None
    for (mixer, mlp), lp, st in zip(cfg.layer_kinds, params["layers"], state):
        x, st, r = _mix(
            x, lp, st, mixer, pos, valid, n_valid.astype(jnp.int32), cfg, window,
            rows_apart, mesh=mesh,
        )
        x, c, rho, pending = _mlp(x, lp, mlp, valid, cfg, mesh, rho, pending)
        counters = counters + c
        read = read + r
        out_state.append(st)
    counters = jnp.concatenate([counters, read])
    # A prediction module's state lies behind the stack's and is its own
    # to move (``mtp_forward``).
    return x, tuple(out_state) + tuple(state[cfg.n_layers :]), counters


def logits(params: Params, cfg: HybridConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """Final norm and the head, accumulated in float32: the untied
    matrix, or the embedding where the model ties its head to it."""
    h = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum(
            "...d,vd->...v", h, params["embed"], preferred_element_type=F32
        )
    return jnp.einsum(
        "...d,dv->...v", h, params["lm_head"], preferred_element_type=F32
    )


# -- the prediction module ---------------------------------------------------------


def _mtp_input(params: Params, cfg: HybridConfig, hidden, next_tokens):
    """``W_eh [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(hbar_t)]``: ``hidden``
    (b, s, D) the stack's output before its final norm, ``hbar`` after."""
    mp = params["mtp"]
    with jax.named_scope("mtp/embed"):
        e = rms_norm(params["embed"][next_tokens], mp["enorm"], cfg.norm_eps)
    with jax.named_scope("mtp/eh_proj"):
        hbar = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
        both = jnp.concatenate([e, rms_norm(hbar, mp["hnorm"], cfg.norm_eps)], axis=-1)
        return jnp.dot(both.astype(hidden.dtype), mp["eh_proj"])


def mtp_forward(
    params: Params,
    cfg: HybridConfig,
    hidden: jnp.ndarray,
    next_tokens: jnp.ndarray,
    pos: jnp.ndarray,
    valid: jnp.ndarray,
    rows: Mapping[str, jnp.ndarray],
    *,
    window: int,
    mesh=None,
    rows_apart: bool = False,
):
    """The prediction module at positions ``pos`` (b, s): ``hidden`` the
    stack's output there (``forward``'s), ``next_tokens`` the token that
    follows each, ``valid`` (b, s) which of them count (a position that
    does not writes no row and routes nowhere; ``pos`` may be -1 there),
    ``rows`` its block's rows (K/V, or latent rows and index keys: the
    kind ``cfg.mtp_kind`` names).  Returns (the module's hidden (b, s, D),
    whose :func:`mtp_logits` at ``t`` predict token ``t + 2``; rows;
    counters as ``forward``'s)."""
    lp = params["mtp"]["layer"]
    n_valid = valid.sum(-1).astype(jnp.int32)
    u = _mtp_input(params, cfg, hidden, next_tokens)
    with jax.named_scope("mtp/block"):
        x, rows, read = _mix(
            u, lp, rows, cfg.mtp_kind[0], pos, valid, n_valid, cfg, window, rows_apart,
            site="mtp_", mesh=mesh,
        )
        x, counters, _, _ = _mlp(x, lp, cfg.mtp_kind[1], valid, cfg, mesh)
    return x, rows, jnp.concatenate([counters, read])


def mtp_logits(params: Params, cfg: HybridConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """The module's own final norm, then the stack's head (shared)."""
    with jax.named_scope("mtp/head"):
        h = rms_norm(hidden, params["mtp"]["final_norm"], cfg.norm_eps)
        return jnp.einsum(
            "...d,dv->...v", h, params["lm_head"], preferred_element_type=F32
        )


# -- presets --------------------------------------------------------------------

# The language model's keys of inclusionAI/Ling-3.0-flash-VL's config.json
# that give it its shape (the file of the benchmark's configuration holds
# them all; the vision tower has no key there and is not modelled).
LING_FLASH_VL = {
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "num_experts": 512,
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "score_function": "sigmoid", "num_attention_heads": 32, "head_dim": 128,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
    "vocab_size": 157184, "layer_group_size": 6, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5,
}
# One chip's share of a four-chip host, seven layers deep: published
# layers 1-7 (a dense KDA layer, then KDA, KDA, KDA, MLA, KDA, KDA with
# experts), 128 of the 512 experts, a quarter of the vocabulary.
LING_L7E128_CUT = {
    "num_hidden_layers": 7, "first_layer": 1, "first_k_dense_replace": 1,
    "num_experts": 128, "num_experts_published": 512, "vocab_size": 39296,
}
# Every ratio of the cut at sizes a CPU test runs: a period of 6 after a
# dense first layer, 2 of 8 routing groups held, 4 groups kept.
LING_TINY = {
    **LING_FLASH_VL, **LING_L7E128_CUT,
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_experts": 8,
    "num_experts_published": 32, "num_experts_per_tok": 4,
    "num_attention_heads": 4, "head_dim": 16, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "vocab_size": 512, "torch_dtype": "float32",
}


# JetBrains/Mellum2-12B-A2.5B-Instruct's config.json: every key that gives
# the model its shape (``intermediate_size`` is of a 'dense' MLP, which no
# published layer is).
_MELLUM_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
MELLUM2_12B = {
    "model_type": "mellum", "num_hidden_layers": 28, "hidden_size": 2304,
    "intermediate_size": 7168, "moe_intermediate_size": 896,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "attention_bias": False, "hidden_act": "silu",
    "layer_types": _MELLUM_PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "num_experts": 64, "num_experts_per_tok": 8, "norm_topk_prob": True,
    "sliding_window": 1024, "max_position_embeddings": 131072,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
    "rms_norm_eps": 1e-06, "vocab_size": 98304, "tie_word_embeddings": False,
}
# One chip of a three-chip pipeline of whole layers: published layers
# 0-11, three whole periods, every expert and the whole vocabulary.
MELLUM_L12_CUT = {"num_hidden_layers": 12}
# Every ratio at sizes a CPU test runs: a period of 4, two periods deep, a
# window of 16 (prompts are several windows long), YaRN over an original
# context of 32 (so positions past it are reached), 3 of 8 experts a token.
MELLUM_TINY = {
    **MELLUM2_12B, "num_hidden_layers": 8, "hidden_size": 64,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
    "num_experts_per_tok": 3, "sliding_window": 16, "vocab_size": 512,
    "rope_parameters": {
        "full_attention": {
            **MELLUM2_12B["rope_parameters"]["full_attention"],
            "original_max_position_embeddings": 32,
        },
        "sliding_attention": MELLUM2_12B["rope_parameters"]["sliding_attention"],
    },
    "torch_dtype": "float32",
}


# LGAI-EXAONE/K-EXAONE-236B-A23B's config.json: every key that gives the
# model its shape.
K_EXAONE_236B = {
    "model_type": "exaone_moe", "num_hidden_layers": 48, "hidden_size": 6144,
    "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128,
    "hidden_act": "silu", "first_k_dense_replace": 1,
    "layer_types": _MELLUM_PERIOD * 12,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "num_experts": 128, "num_experts_per_tok": 8, "num_shared_experts": 1,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "sliding_window": 128, "max_position_embeddings": 262144,
    "rope_parameters": {"rope_type": "default", "rope_theta": 1000000},
    "num_nextn_predict_layers": 1, "mtp_layer_types": ["full_attention"],
    "rms_norm_eps": 1e-05, "vocab_size": 153600, "tie_word_embeddings": False,
}
# Rank 0's share of an eight-chip layer group: published layers 0-4
# (window + dense, window, window, full, window) and the prediction
# module, 16 of the 128 experts, an eighth of the vocabulary.
K_EXAONE_L5E16_CUT = {
    "num_hidden_layers": 5, "num_experts": 16, "num_experts_published": 128,
    "vocab_size": 19200,
}
# Every ratio at sizes a CPU test runs: a period of 4 after a dense first
# layer (two periods deep), a window of 8 (shorter than any chunk), 4 of
# 16 experts held and 2 a token, one prediction module.
EXAONE_TINY = {
    **K_EXAONE_236B, "num_hidden_layers": 8, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 2,
    "sliding_window": 8, "vocab_size": 512, "torch_dtype": "float32",
}


# mistralai/Mistral-Small-4-119B-2603's config.json: every key that gives
# the language model its shape (``intermediate_size`` is a dense MLP's,
# which no layer is: ``first_k_dense_replace`` 0; the vision tower has no
# key there and is not modelled).
MISTRAL_SMALL_4 = {
    "model_type": "mistral4", "num_hidden_layers": 36, "hidden_size": 4096,
    "intermediate_size": 12288, "moe_intermediate_size": 2048,
    "first_k_dense_replace": 0, "num_attention_heads": 32,
    "num_key_value_heads": 32, "head_dim": 128, "q_lora_rank": 1024,
    "kv_lora_rank": 256, "qk_head_dim": 128, "qk_nope_head_dim": 64,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_interleave": True,
    "attention_bias": False, "mlp_bias": False, "hidden_act": "silu",
    "n_routed_experts": 128, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "sliding_window": None,
    "max_position_embeddings": 1048576,
    "rope_parameters": {
        "rope_type": "yarn", "type": "yarn", "rope_theta": 10000, "factor": 128,
        "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1, "llama_4_scaling_beta": 0.1,
    },
    "rms_norm_eps": 1e-06, "vocab_size": 131072, "tie_word_embeddings": False,
}
# Rank 0's share of a four-chip layer group: published layers 0-5, 32 of
# the 128 experts (the first 32), a quarter of the vocabulary.
MISTRAL4_L6E32_CUT = {
    "num_hidden_layers": 6, "n_routed_experts": 32, "num_experts_published": 128,
    "vocab_size": 32768,
}
# Every ratio at sizes a CPU test runs: 8 of 32 experts held and 2 a
# token, a query rank under the hidden size, YaRN and the query's
# position scale past an original context of 32 (prompts cross it).
MISTRAL4_TINY = {
    **MISTRAL_SMALL_4, "num_hidden_layers": 3, "hidden_size": 64,
    "moe_intermediate_size": 32, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "qk_head_dim": 16, "v_head_dim": 16, "head_dim": 16,
    "n_routed_experts": 8, "num_experts_published": 32, "num_experts_per_tok": 2,
    "vocab_size": 512, "torch_dtype": "float32",
    "rope_parameters": {
        **MISTRAL_SMALL_4["rope_parameters"], "factor": 8,
        "original_max_position_embeddings": 32,
    },
}


# Zyphra/ZAYA1-8B's config.json: every key that gives the model its shape.
ZAYA1_8B = {
    "model_type": "zaya", "num_hidden_layers": 40, "hidden_size": 2048,
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 128, "attention_bias": False,
    "lm_head_bias": False, "hidden_act": "silu", "cca_time0": 2, "cca_time1": 2,
    "layer_types": ["hybrid"] * 40, "num_experts": 16, "num_experts_per_tok": 1,
    "router_hidden_size": 256, "partial_rotary_factor": 0.5,
    "sliding_window": None, "max_position_embeddings": 131072,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
        "rope_type": "default",
    },
    "rms_norm_eps": 1e-05, "vocab_size": 262272, "tie_word_embeddings": True,
}
# The first of two pipeline stages: published layers 0-19, every expert,
# the whole tied vocabulary.
ZAYA1_L20_CUT = {"num_hidden_layers": 20}
# Every ratio at sizes a CPU test runs: a query latent of half the hidden
# size (4 heads of 16 under 128) on 2 key-value heads, so that the grouped
# mean and the value shift have two heads each; three layers, so that the
# router's state is handed on twice; 8 experts, one a token.
ZAYA_TINY = {
    **ZAYA1_8B, "num_hidden_layers": 3, "hidden_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
    "router_hidden_size": 16, "vocab_size": 512, "torch_dtype": "float32",
}


# nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's config.json: every key
# that gives the model its shape (``intermediate_size`` is a dense MLP's,
# which no layer is; ``rope_theta`` and ``partial_rotary_factor`` are its
# class's defaults and turn nothing: the attention layers are not rotated).
NEMOTRON3_SUPER = {
    "model_type": "nemotron_h", "num_hidden_layers": 88, "hidden_size": 4096,
    "hybrid_override_pattern": (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
    ),
    "mtp_hybrid_override_pattern": "*E", "num_nextn_predict_layers": 1,
    "mamba_num_heads": 128, "mamba_head_dim": 64, "expand": 2, "n_groups": 8,
    "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False, "use_conv_bias": True,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "attention_bias": False, "rope_theta": 10000, "partial_rotary_factor": 1,
    "sliding_window": None, "max_position_embeddings": 262144,
    "intermediate_size": 2688, "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "n_routed_experts": 512, "n_shared_experts": 1, "num_experts_per_tok": 22,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 5, "mlp_hidden_act": "relu2", "mlp_bias": False,
    "use_bias": False, "layer_norm_epsilon": 1e-05, "norm_eps": 1e-05,
    "vocab_size": 131072, "tie_word_embeddings": False,
}
# Rank 0's share of the first of eight pipeline stages, every layer shared
# by four chips: published layers 0-10 (``MEMEMEM*EME``: five Mamba-2
# mixers, five expert layers, one attention layer), 128 of the 512 experts
# (the first 128), a quarter of the vocabulary.
NEMOTRON3_L11E128_CUT = {
    "num_hidden_layers": 11, "n_routed_experts": 128, "num_experts_published": 512,
    "vocab_size": 32768,
}
# Every ratio at sizes a CPU test runs: ``MEM*EM`` (all three kinds, a
# layer that is a mixer alone, a state carried past attention), 8 heads of
# 16 in 2 groups with a state of 16 and blocks of 8 tokens, 4 query heads
# on 2 key-value heads, 4 of 8 experts held and 3 a token in a latent of
# half the hidden size.
NEMOTRON_H_TINY = {
    **NEMOTRON3_SUPER, "num_hidden_layers": 6, "hybrid_override_pattern": "MEM*EM",
    "hidden_size": 64, "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "chunk_size": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 64,
    "n_routed_experts": 4, "num_experts_published": 8, "num_experts_per_tok": 3,
    "vocab_size": 512, "torch_dtype": "float32",
}


# dots-studio/dots3-note-prev's config.json: every key that gives the
# language model its shape (the vision tower, the audio encoder and the
# prediction module have no key there and are not modelled).
_DOTS3_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
DOTS3_NOTE_PREV = {
    "model_type": "dots3_note", "num_hidden_layers": 46, "hidden_size": 5120,
    "intermediate_size": 13824, "moe_intermediate_size": 1536,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "layer_types": ["full_attention"] * 2 + _DOTS3_PERIOD * 11,
    "num_attention_heads": 128, "num_key_value_heads": 128, "q_lora_rank": 1024,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "rope_theta": 80000000, "rope_scaling": None,
    "attention_gate_type": "headwise", "apply_mla_qkv_lora_rescale": True,
    "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
    "sliding_window_size": 513, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
    "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128, "swa_rope_theta": 50000,
    "swa_attention_gate_type": "headwise", "attention_bias": False,
    "hidden_act": "silu", "n_routed_experts": 256, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "max_position_embeddings": 524288, "rms_norm_eps": 1e-05,
    "vocab_size": 152064, "tie_word_embeddings": False,
}
# Rank 0's share of the first of eight pipeline stages, every layer shared
# by eight chips: published layers 0-5 (full + dense, full, sliding,
# sliding, sliding, full), 32 of the 256 experts (the first 32), an eighth
# of the vocabulary.
DOTS3_L6E32_CUT = {
    "num_hidden_layers": 6, "n_routed_experts": 32, "num_experts_published": 256,
    "vocab_size": 19008,
}
# Every ratio at sizes a CPU test runs: the same six layers, the two kinds
# with heads, ranks, head sizes and thetas that all differ, an indexer of 2
# heads that keeps 24 rows (prompts of 80 cross it), a window of 13 (shorter
# than a chunk of 16; the ring turns over six times), 2 of 16 experts held
# (an eighth, as in the cut) and 2 a token.
DOTS3_NOTE_TINY = {
    **DOTS3_NOTE_PREV, **DOTS3_L6E32_CUT, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "index_n_heads": 2, "index_head_dim": 16, "index_topk": 24,
    "sliding_window_size": 13, "swa_num_attention_heads": 2,
    "swa_num_key_value_heads": 2, "swa_q_lora_rank": 16, "swa_kv_lora_rank": 24,
    "swa_qk_nope_head_dim": 16, "swa_qk_rope_head_dim": 4, "swa_v_head_dim": 8,
    "n_routed_experts": 2, "num_experts_published": 16, "num_experts_per_tok": 2,
    "vocab_size": 512, "torch_dtype": "float32",
}


# deepseek-ai/DeepSeek-V3.2's config.json: every key that gives the model
# its shape (``ep_size`` and ``max_position_embeddings`` shape nothing here).
DEEPSEEK_V32 = {
    "model_type": "deepseek_v32", "num_hidden_layers": 61, "hidden_size": 7168,
    "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "first_k_dense_replace": 3, "moe_layer_freq": 1, "num_attention_heads": 128,
    "num_key_value_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
    "attention_bias": False, "hidden_act": "silu", "n_routed_experts": 256,
    "n_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
    "max_position_embeddings": 163840, "rope_theta": 10000,
    "rope_scaling": {
        "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
    },
    "rms_norm_eps": 1e-06, "vocab_size": 129280, "tie_word_embeddings": False,
}
# Rank 0's share of the first pipeline stage, every layer shared by sixteen
# chips: published layers 0-4 with ONE leading dense layer standing for the
# three (they count once), 16 of the 256 experts (the first 16: half of
# routing group 0), an eighth of the vocabulary; the module is held here.
DEEPSEEK_V32_L5E16_CUT = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
    "num_experts_published": 256, "vocab_size": 16160,
}
# Every ratio at sizes a CPU test runs: a dense first layer and two expert
# layers, an indexer of 2 heads that keeps 24 rows (prompts of 80 cross
# it), YaRN past an original context of 32, 16 router outputs in 2 groups
# of which 1 is kept, 4 held (HALF a group, as in the cut) and 2 a token,
# one prediction module.
DEEPSEEK_V32_TINY = {
    **DEEPSEEK_V32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "index_n_heads": 2, "index_head_dim": 16, "index_topk": 24,
    "n_routed_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 2,
    "n_group": 2, "topk_group": 1, "vocab_size": 512, "torch_dtype": "float32",
    "rope_scaling": {
        **DEEPSEEK_V32["rope_scaling"], "factor": 8,
        "original_max_position_embeddings": 32,
    },
}


# meituan-longcat/LongCat-Flash-Chat's config.json: every key that gives
# the model its shape (the prediction module has no key there and is not
# modelled).
LONGCAT_FLASH_CHAT = {
    "model_type": "longcat_flash", "num_layers": 28, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_attention_heads": 64, "attention_method": "MLA", "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "attention_bias": False, "n_routed_experts": 512, "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12, "routed_scaling_factor": 6,
    "max_position_embeddings": 131072, "rope_theta": 10000000,
    "rms_norm_eps": 1e-05, "vocab_size": 131072,
}
# Rank 0's share of the first of seven pipeline stages, every layer shared
# by 32 chips: published layers 0-3 (eight latent sublayers), 16 of the 512
# real experts (the first 16), all 256 identity outputs of the router, an
# eighth of the vocabulary.
LONGCAT_L4E16_CUT = {
    "num_layers": 4, "n_routed_experts": 16, "num_experts_published": 512,
    "vocab_size": 16384,
}
# Every ratio at sizes a CPU test runs: two published layers (four
# sublayers: a branch crosses a sublayer twice), 4 of 16 real experts held
# beside 8 identity outputs (a third of the router, as published) and 3 a
# token, a query rank under the hidden size.
LONGCAT_FLASH_TINY = {
    **LONGCAT_FLASH_CHAT, "num_layers": 2, "hidden_size": 64,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "num_experts_published": 16, "zero_expert_num": 8,
    "moe_topk": 3, "vocab_size": 512, "torch_dtype": "float32",
}


def ling_flash_vl_l7e128() -> HybridConfig:
    return from_hf_config({**LING_FLASH_VL, **LING_L7E128_CUT}, max_len=2048)


def ling_tiny() -> HybridConfig:
    return from_hf_config(LING_TINY, max_len=256, kv_dtype="float32")


def mellum2_12b_l12() -> HybridConfig:
    return from_hf_config({**MELLUM2_12B, **MELLUM_L12_CUT}, max_len=8192)


def mellum_tiny() -> HybridConfig:
    return from_hf_config(MELLUM_TINY, max_len=256, kv_dtype="float32")


def k_exaone_236b_l5e16() -> HybridConfig:
    return from_hf_config(
        {**K_EXAONE_236B, **K_EXAONE_L5E16_CUT}, max_len=8192, draft="mtp"
    )


def exaone_tiny() -> HybridConfig:
    return from_hf_config(EXAONE_TINY, max_len=256, kv_dtype="float32", draft="mtp")


def mistral_small_4_l6e32() -> HybridConfig:
    return from_hf_config({**MISTRAL_SMALL_4, **MISTRAL4_L6E32_CUT}, max_len=32768)


def mistral4_tiny() -> HybridConfig:
    # Blocks of 16 latent rows: shorter than the windows the tests use.
    return dataclasses.replace(
        from_hf_config(MISTRAL4_TINY, max_len=256, kv_dtype="float32"),
        latent_block=16, latent_decode_block=16,
    )


def zaya1_8b_l20() -> HybridConfig:
    return from_hf_config({**ZAYA1_8B, **ZAYA1_L20_CUT}, max_len=8192)


def zaya_tiny() -> HybridConfig:
    return from_hf_config(ZAYA_TINY, max_len=256, kv_dtype="float32")


def nemotron3_super_l11e128() -> HybridConfig:
    return from_hf_config({**NEMOTRON3_SUPER, **NEMOTRON3_L11E128_CUT}, max_len=8192)


def nemotron_h_tiny() -> HybridConfig:
    return from_hf_config(NEMOTRON_H_TINY, max_len=256, kv_dtype="float32")


def dots3_note_prev_l6e32() -> HybridConfig:
    return from_hf_config({**DOTS3_NOTE_PREV, **DOTS3_L6E32_CUT}, max_len=16384)


def dots3_note_tiny() -> HybridConfig:
    # Blocks of 16 rows: shorter than the windows the tests use.
    return dataclasses.replace(
        from_hf_config(DOTS3_NOTE_TINY, max_len=256, kv_dtype="float32"),
        latent_block=16, latent_decode_block=16,
    )


def deepseek_v32_l5e16() -> HybridConfig:
    return from_hf_config(
        {**DEEPSEEK_V32, **DEEPSEEK_V32_L5E16_CUT}, max_len=16384, draft="mtp"
    )


def deepseek_v32_tiny() -> HybridConfig:
    # Blocks of 16 rows: shorter than the windows the tests use.
    return dataclasses.replace(
        from_hf_config(DEEPSEEK_V32_TINY, max_len=256, kv_dtype="float32", draft="mtp"),
        latent_block=16, latent_decode_block=16,
    )


def longcat_flash_chat_l4e16() -> HybridConfig:
    return from_hf_config({**LONGCAT_FLASH_CHAT, **LONGCAT_L4E16_CUT}, max_len=16384)


def longcat_flash_tiny() -> HybridConfig:
    # Blocks of 16 latent rows: shorter than the windows the tests use.
    return dataclasses.replace(
        from_hf_config(LONGCAT_FLASH_TINY, max_len=256, kv_dtype="float32"),
        latent_block=16, latent_decode_block=16,
    )


PRESETS = {
    "ling-3.0-flash-vl-l7e128": ling_flash_vl_l7e128,
    "ling-tiny": ling_tiny,
    "mellum2-12b-a2.5b-l12": mellum2_12b_l12,
    "mellum-tiny": mellum_tiny,
    "k-exaone-236b-a23b-l5e16": k_exaone_236b_l5e16,
    "exaone_moe-tiny": exaone_tiny,
    "mistral-small-4-119b-l6e32": mistral_small_4_l6e32,
    "mistral4-tiny": mistral4_tiny,
    "zaya1-8b-l20": zaya1_8b_l20,
    "zaya-tiny": zaya_tiny,
    "nemotron-3-super-120b-a12b-l11e128": nemotron3_super_l11e128,
    "nemotron_h-tiny": nemotron_h_tiny,
    "dots3-note-prev-l6e32": dots3_note_prev_l6e32,
    "dots3_note-tiny": dots3_note_tiny,
    "deepseek-v3.2-l5e16": deepseek_v32_l5e16,
    "deepseek_v32-tiny": deepseek_v32_tiny,
    "longcat-flash-chat-l4e16": longcat_flash_chat_l4e16,
    "longcat_flash-tiny": longcat_flash_tiny,
}
