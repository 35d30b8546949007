"""Model spec: what the paged engine has to know about a decoder's layers.

The step functions of :mod:`tpulab.engine.paged_steps` run ONE layer block
(``paged_steps._layer_block``) for every model they serve; a :class:`ModelSpec`
tells it the attention kind and its widths, which layers carry a dense FFN
and which a routed expert FFN, the RMSNorm epsilon, and with the attention
kind the *cache-entry kind* the page store holds:

``"kv"``      K and V rows of ``n_kv_heads * head_dim`` values (MHA/GQA);
``"latent"``  one row ``[c_kv ; k_rope]`` of ``kv_lora_rank +
              qk_rope_head_dim`` values a token a layer (multi-head latent
              attention, served in the absorbed form);
``"kv_index"``  K and V rows as ``"kv"``, and beside them, under the same
              page ids, one *index key* of ``index_dim`` values a token a
              layer: what a learned indexer scores a query against to
              choose the ``index_topk`` keys the query attends to.

A layer's *mixer* is attention, a Mamba-1 selective state-space block
(``"mamba"``: Jamba's, a state a CHANNEL), a Mamba-2 block (``"mamba2"``:
Nemotron-H's, a state a HEAD under a scalar decay), a Gated DeltaNet
linear-attention block (``"gdn"``) or nothing at all (``"none"``: the layer
is its feed-forward part alone) (``mixers``).  A Mamba or Gated DeltaNet
layer leaves no pages: it keeps a per-lane recurrent state of fixed size in
the engine's lane-state store (``state_kind``: ``"mamba"``, the SSM state
``d_state x d_inner`` in float32; ``"gdn"``, a matrix ``gdn_k_dim x
gdn_v_dim`` in float32 a value head; ``"mamba2"``, a matrix ``m2_head_dim x
m2_state`` in float32 a head; each with the last ``d_conv - 1`` inputs of
the layer's causal convolution), so only attention layers own a layer of
the page store (``store_layer``).  A layer's feed-forward part is likewise a
dense FFN, an expert block or nothing (``layer_kinds`` ``"none"``: the layer
is its mixer alone, ONE norm and ONE residual).

The dense decoder the engine has always served is :func:`dense_spec` with
today's constants (epsilon 1e-6, ``head_dim = d_model // n_heads``); the
step functions build it themselves when no spec is passed, so a dense model
compiles to the programs it always had.

``glm4_moe_lite`` (GLM-4.7-Flash, DeepSeek-V3-style): MLA with a low-rank
query, a leading dense SwiGLU layer, then expert layers with a sigmoid
router, bias-corrected top-k selection, normalised and scaled weights and
one always-on shared expert.  :func:`glm4_moe_lite_spec` reads the
published ``config.json`` keys; :func:`init_params` draws a parameter tree
in the layout the layer block reads:

=============  ==========================================================
``wq_a``       ``(d_model, q_lora_rank)``
``q_norm``     ``{"scale": (q_lora_rank,)}``
``wq_b``       ``(q_lora_rank, n_heads * (qk_nope + qk_rope))``, a head's
               columns ``[nope | rope]``
``wkv_a``      ``(d_model, kv_lora_rank + qk_rope)``, columns
               ``[c_kv | k_rope]``
``kv_norm``    ``{"scale": (kv_lora_rank,)}``
``w_uk``       ``(n_heads, qk_nope, kv_lora_rank)``: the key half of the
               published ``kv_b_proj``, per head, ready to be absorbed
               into the query
``w_uv``       ``(n_heads, kv_lora_rank, v_head_dim)``: its value half
``wo``         ``(n_heads * v_head_dim, d_model)``
``w1 w3 w2``   dense SwiGLU (gate, up, down) on dense layers
``moe``        ``router (d_model, E)``, ``bias (E,)``, ``w13 (E, d_model,
               2 * moe_ff)`` = ``[gate | up]``, ``w2 (E, moe_ff, d_model)``
``shared``     ``w1 w3 w2`` of width ``n_shared * moe_ff``
=============  ==========================================================

:func:`split_kv_b` turns a published ``kv_b_proj`` into ``w_uk``/``w_uv``.
RoPE is the engine's rotate-half convention over the ``qk_rope`` columns.
The multi-token-prediction layer of the published model is not built.

``jamba`` (AI21-Jamba2-3B): Mamba-1 mixers (mixer ``"mamba"``; Mamba-2 is
``nemotron_h``'s, at the end of this text) with RMSNorm on dt, B and C, a
full-attention GQA mixer without positional encoding every
``attn_layer_period`` layers, a dense SwiGLU FFN on every layer
(``num_experts`` 1), a tied output head.  :func:`jamba_spec` reads the
published keys.  An attention layer has the dense decoder's leaves
(``wqkv`` = ``[q | k | v]``, ``wo``); a Mamba layer has, under ``mamba``:

=============  ==========================================================
``in_proj``    ``(d_model, 2 * d_inner)``, columns ``[u | z]``
``conv_w``     ``(d_conv, d_inner)``: tap ``k`` weighs the input ``d_conv
               - 1 - k`` tokens back (the published depthwise ``conv1d``
               weight ``(d_inner, 1, d_conv)``, transposed)
``conv_b``     ``(d_inner,)``
``x_proj``     ``(d_inner, dt_rank + 2 * d_state)``, columns ``[r | B | C]``
``dt_norm`` ``b_norm`` ``c_norm``   ``{"scale"}`` of ``dt_rank``, ``d_state``,
               ``d_state``
``dt_proj``    ``(dt_rank, d_inner)``; ``dt_bias (d_inner,)``
``a_log``      ``(d_state, d_inner)``: the published ``A_log``, transposed
               (channels minor, as the state is stored)
``d``          ``(d_inner,)``
``out_proj``   ``(d_inner, d_model)``
=============  ==========================================================

``keye_vl2`` (the decoder of Keye-VL-2.0-30B-A3B): a Qwen3-MoE-shaped GQA
decoder (RMSNorm over each head of q and k before RoPE, softmax-routed
experts renormalised over the chosen k, no shared expert) whose every layer
carries a DeepSeek-Sparse-Attention indexer: ``index_heads`` index queries
of ``index_dim`` and ONE index key a token; a query scores every key at or
before it, ``I_ts = (index_heads * index_dim)^-0.5 * sum_i c_ti * relu(a_ti
. b_s)`` in float32, and attends to the ``index_topk`` keys of largest
``I`` (to all of them until the context passes ``index_topk``), one set a
token shared by all heads.  :func:`keye_vl2_spec` reads the published keys
and ``sa_config``.  A layer has ``wqkv`` = ``[q | k | v]``, ``q_norm`` /
``k_norm`` ``{"scale": (head_dim,)}``, ``wo``, the ``moe`` leaves without
``bias`` and no ``shared``, and under ``indexer``:

=============  ==========================================================
``wq``         ``(d_model, index_heads * index_dim)``: the index queries
               ``a``, from the layer's normed input, RoPE over
               ``index_dim``
``wk``         ``(d_model, index_dim)``: the index key ``b``, LayerNorm
               (``k_norm`` ``{"scale", "bias"}``) then RoPE
``ww``         ``(d_model, index_heads)``: the heads' weights ``c``
=============  ==========================================================

``qwen3_next`` (Qwen3-Next-80B-A3B): three Gated DeltaNet layers, then one
gated softmax-attention layer (``full_attention_interval``); every layer's
FFN is an expert layer (softmax router renormalised over the chosen k) with
one shared expert scaled by a sigmoid gate.  :func:`qwen3_next_spec` reads
the published keys; ``first`` / ``held`` give the contiguous share of the
routed experts this device holds (the router keeps every column).  Norm
scales hold ``1 + w`` of the published zero-centred weights.  An attention
layer has ``wqkv`` = ``[q | k | v]`` where a query head's columns are
``[query head_dim | gate head_dim]`` (the published ``q_proj``), ``q_norm``
/ ``k_norm``, ``wo``; RoPE turns the first ``rotary_dim`` columns of a
head.  Every layer has ``moe`` with ``router (d_model, E)`` and ``w13`` /
``w2`` of the ``held`` experts, and ``shared`` ``w1 w3 w2`` plus ``gate
(d_model, 1)``.  A Gated DeltaNet layer has, under ``gdn``:

=============  ==========================================================
``in_qkvz``    ``(d_model, 2 * Hk * d_k + 2 * Hv * d_v)``, columns ``[q | k |
               v | z]``, heads in order inside each (the published
               ``in_proj_qkvz`` interleaves them key head by key head, ``[q
               d_k | k d_k | v (Hv / Hk) * d_v | z (Hv / Hk) * d_v]``:
               :func:`split_qkvz` turns one into the other, once, at load
               time; taken apart on the activations XLA copied the whole
               matrix every dispatch, 0.13 ms a layer on a v5e)
``in_ba``      ``(d_model, 2 * Hv)``, columns ``[b | a]`` (published: key
               head by key head ``[b Hv / Hk | a Hv / Hk]``;
               :func:`split_qkvz` again)
``conv_w``     ``(d_conv, 2 * Hk * d_k + Hv * d_v)`` over the channels
               ``[q | k | v]`` (heads in order inside each), tap ``k``
               weighing the input ``d_conv - 1 - k`` tokens back; no bias
``a_log``      ``(Hv,)``; ``dt_bias (Hv,)``
``norm``       ``{"scale": (d_v,)}``: the gated RMSNorm of a head's output
               (plain scale, not ``1 + w``)
``out_proj``   ``(Hv * d_v, d_model)``
=============  ==========================================================

The multi-token-prediction layer of the published model is not built.

``evabyte`` (EvaByte 6.5B, ``attention_class`` ``eva``): a dense MHA decoder
over bytes whose attention keeps a context in two forms in ONE softmax: the
rows of the query's own window of ``eva_window`` positions whole, and every
EARLIER window as ``eva_window / eva_chunk`` summary rows, one ``(k~, v~)``
a chunk: ``k~ = sum_m softmax_m(mu_h . k_m) k_m`` and ``v~ = sum_m
softmax_m(phi_h . k_m) v_m`` over the chunk's roped keys, ``mu`` and ``phi``
a learned vector a head.  So a lane's ROWS are not its positions: position
``p`` lives at row ``p - (p // window) * (window - window / chunk)``
(:meth:`ModelSpec.cache_row`), and when a window completes its rows are
compacted in place into its summaries (:func:`tpulab.engine.paged_steps.
paged_eva_compact`) before the next position is written.  RoPE turns ``q``
and ``k`` at the TRUE position; the page store, the ragged kernels and their
causal mask run on the row.  :func:`evabyte_spec` reads the published keys.
A layer has the dense decoder's leaves (``wqkv`` = ``[q | k | v]``, ``wo``,
``w1 w3 w2``) and ``eva_mu`` / ``eva_phi`` ``(n_heads, head_dim)``; norm
scales hold ``1 + w`` (``norm_add_unit_offset``).  The published head is
``num_pred_heads x vocab_size`` rows: ``lm_head (d_model, vocab)`` is
prediction head 0, the one a plain ``generate`` reads, and ``mtp_heads
(d_model, (num_pred_heads - 1) * vocab)`` the further heads, held and not
run (:func:`split_pred_heads`).

``longcat_flash`` (LongCat-Flash-Chat): a published layer is TWO latent
attentions, TWO dense SwiGLU FFNs and ONE expert block on a shortcut: ``a1
= x + MLA_0(norm(x))``, ``h1 = norm(a1)``, ``m = MoE(h1)``, ``b1 = a1 +
FFN_0(h1)``, ``a2 = b1 + MLA_1(norm(b1))``, ``out = a2 + FFN_1(norm(a2)) +
m``: the expert block reads the first attention's output and is added at
the layer's END.  It is served as two engine layers, each with its own
layer of the latent page store: kinds ``("shortcut", "dense")``, the first
running the expert block on its dense FFN's input and handing ``m`` to the
second (:func:`tpulab.engine.paged_steps._layer_block`).  The router has
``n_routed_experts + zero_expert_num`` columns, the last ``zero_experts``
of them *identity experts* that return their input and hold no weights:
``s = softmax(h W_r)`` in float32 over every column, the ``top_k`` columns
of largest ``s + b`` are chosen, a chosen column weighs
``routed_scaling_factor * s`` (not renormalised), ``MoE(h) = sum over the
chosen FFN experts of w_j SwiGLU_j(h) + (sum over the chosen identity
columns of w_j) h`` (router kind ``"softmax_bias"``).
:func:`longcat_flash_spec` reads the published keys; ``first`` / ``held``
give the share of the FFN experts held here.  A layer has the MLA leaves of
``glm4_moe_lite`` above, ``w1 w3 w2``, and on the first of a pair ``moe``
(``router (d_model, E + Z)``, ``bias (E + Z,)``, ``w13`` / ``w2`` of the
held experts).  The published factors ``mla_scale_q_lora`` (``q`` times
``(d_model / q_lora_rank)^0.5``) and ``mla_scale_kv_lora`` (the normed
``c_kv`` times ``(d_model / kv_lora_rank)^0.5``) and the published
interleaved RoPE are taken up once, at load time, by
:func:`longcat_flash_layout`:

=============  ==========================================================
``wq_b``       the published ``q_b_proj`` times the query factor, a head's
               rope columns reordered ``[even | odd]`` (interleaved pairs
               become the engine's rotate-half pairs)
``wkv_a``      the published ``kv_a_proj_with_mqa``, its rope columns
               reordered the same way
``w_uk w_uv``  :func:`split_kv_b` of the published ``kv_b_proj``, both
               times the latent factor (the latent row stays the plain
               normed ``c_kv``)
=============  ==========================================================

so the latent kernel, its program and the cache entry are those of
``glm4_moe_lite``.

``xing4_0`` (Xing4.0-29B-A4B): the ``glm4_moe_lite`` layer (latent attention,
leading dense layers, sigmoid-routed experts and a shared expert) inside
*manifold-constrained hyper-connections* (mHC, arXiv:2512.24880): a token's
residual state is ``hc_mult`` streams ``X (n, d_model)``, and each SUBLAYER
(the attention, the FFN) reads one row ``u = sum_i h_pre[i] X[i]`` and writes
``X' = H_res X + outer(h_post, F(norm(u)))``; ``h_pre``, ``h_post`` and the
``n x n`` matrix ``H_res`` are computed for every token from the normed,
flattened streams, ``H_res`` projected onto the doubly stochastic matrices
by ``hc_sinkhorn_iters`` Sinkhorn sweeps
(:func:`tpulab.engine.paged_steps._mhc_pre`).  The streams start as copies
of the embedding and end as their sum.  A layer has the ``glm4_moe_lite``
leaves and, under ``hc_attn`` and ``hc_ffn`` (one a sublayer):

=============  ==========================================================
``norm``       ``{"scale": (n * d_model,)}``: RMSNorm over all the streams'
               values, epsilon ``hc_eps``
``phi``        ``(n * d_model, 2 n + n * n)``, columns ``[pre | post |
               res]`` (``res`` row-major: entry ``(i, j)`` weighs stream
               ``j`` in new stream ``i``)
``alpha``      ``(3,)``: the scalars on the three projections
``bias``       ``(2 n + n * n,)``, laid out as ``phi``'s columns
=============  ==========================================================

RoPE's frequencies are YaRN's (``rope_scaling``; :meth:`ModelSpec.
rope_inv_freq`), and the factor YaRN puts on the softmax scale is folded
into ``wq_b`` at load time (:func:`mla_scales`, :func:`scale_queries`), so
the latent kernel's call is ``glm4_moe_lite``'s.  The prediction layer is
not built.

``zaya`` (ZAYA1-8B): every layer is *compressed convolutional attention*
(CCA, arXiv:2510.04476) on K/V pages AND a lane state, then an expert block
behind an MLP router.  CCA: ``c = [q~ ; k~] = h [W_q | W_k]`` (the query
heads first); two causal convolutions over the sequence, depthwise
(``cca_taps[0]`` taps a channel) then grouped by head (``cca_taps[1]`` taps
of one ``head_dim x head_dim`` block a head); a q-k mean from the
PRE-convolution ``c`` added to both; an L2 norm a head in float32 (``q``
times ``sqrt(head_dim)``, ``k`` times ``tau sqrt(head_dim)``, ``tau`` a
learned scalar a KV head); RoPE over the first
``rotary_dim`` columns; ``v = [h_t W_v1 ; h_(t-1) W_v2]`` cut into the KV
heads in that order.  K and V rows go to the lane's pages (mixer ``"cca"``
owns a layer of the page store) and the convolutions' tails and the
shifted value's to the lane-state store (``state_kind`` ``"cca"``: the same
layer owns a layer of that too).  Each sublayer's residual is *scaled*
(``res_scale``): ``x <- s_r (x + b_r) + s_o (f(norm(x)) + b_o)``.  The
router (kind ``"mlp"``) is ``r = h W_d + b_d`` (``router_width``), *depth
averaging* ``r <- r + gamma r_prev`` with the state the layer before handed
on (after ITS averaging; the first expert layer has none), an
RMSNorm, two GELU layers of ``router_width`` and a projection onto the
router's columns, ``p = softmax`` in float32, the ``top_k`` of ``p + bias``
chosen and weighted by ``p``; the last ``zero_experts`` columns are the
skip column(s) (``p_e h``).  :func:`zaya_spec` reads the published keys.
A layer has ``wo (n_heads * head_dim, d_model)``, ``res_attn`` / ``res_ffn``,
``moe`` and, under ``cca``, the first five rows of:

=============  ==========================================================
``in_proj``    ``(d_model, (n_heads + 2 * n_kv_heads) * head_dim)``,
               columns ``[q~ | k~ | v1 | v2]`` (``v1`` and ``v2`` each
               ``n_kv_heads * head_dim / 2`` wide)
``conv0_w``    ``(cca_taps[0], (n_heads + n_kv_heads) * head_dim)``: tap
               ``j`` weighs the input ``cca_taps[0] - 1 - j`` tokens back
               (the published depthwise ``conv1d`` weight, transposed);
               ``conv0_b`` a channel
``conv1_w``    ``(cca_taps[1], n_heads + n_kv_heads, head_dim, head_dim)``:
               tap ``j``'s block of head ``g``, inputs by outputs (the
               published grouped ``conv1d`` weight ``(out, in / groups,
               taps)``, transposed); ``conv1_b`` a channel
``tau``        ``(n_kv_heads,)``
``res_attn`` ``res_ffn``   ``{"s_r", "b_r", "s_o", "b_o"}`` of ``d_model``
``moe``        ``router {"down" (d_model, W), "down_b" (W,), "gamma" (W,)
               (not on layer 0), "norm" {"scale" (W,)}, "w1" "w2" (W, W),
               "b1" "b2" (W,), "w3" (W, E + Z)}``, ``bias (E + Z,)``,
               ``w13`` / ``w2`` of the ``E`` FFN experts
=============  ==========================================================

The head is tied to the embedding (no ``lm_head``).

``mellum`` (Mellum2-12B-A2.5B): a Qwen3-MoE-shaped GQA decoder (RMSNorm over
each head of q and k before RoPE, softmax-routed experts renormalised over
the chosen k, no shared expert, an untied head) whose layers are of two
attention KINDS (``attn_kinds``, from the published ``layer_types``): a
``"full"`` layer sees every key at or before the row, a ``"window"`` layer
the ``window`` keys that end at it (key ``j`` from position ``i`` iff ``i -
window < j <= i``).  RoPE is a layer kind's too: the window layers turn by
``theta^(-2j / d)``, the full layers by YaRN's table
(:meth:`ModelSpec.rope_inv_freq`) with YaRN's factor on cos and sin
(``rope_factor``; ``q . k`` carries its square).  The page store is then two
LAYER GROUPS (:attr:`ModelSpec.page_groups`: ``"full"`` and ``"window"``),
each with its own array, free extents, reference counts and table a lane
(:mod:`tpulab.engine.kv_pool`), ``store_layer`` a layer's index in its
group: a window layer's pages behind its window go back to its group while
the request lives, and the full layers keep theirs.  :func:`mellum_spec`
reads the published keys.  A layer has ``wqkv`` = ``[q | k | v]``, ``q_norm``
/ ``k_norm`` ``{"scale": (head_dim,)}``, ``wo`` and the ``moe`` leaves
without ``bias`` and no ``shared``, whatever its kind.  The prediction head
the model's description names has no key in its config and is not built.

``nemotron_h`` (NVIDIA-Nemotron-3-Nano-30B-A3B): every layer is ONE sublayer
by the letter of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*``
GQA attention without positional rotation, ``E`` an expert block; ``x <- x +
f(RMSNorm(x))``, one norm (``ln1`` of a mixer layer, ``ln2`` of an expert
layer) and one residual a layer, an untied head.  ``M*`` stand side by side
in the pattern, so the letters cannot be folded into (mixer, FFN) pairs:
``mixers`` is ``"none"`` on an ``E`` layer and ``layer_kinds`` ``"none"`` on
the others.  Mamba-2: ``[z | xBC | dt] = h W_in``; ``xBC <- silu(conv(xBC) +
b)`` depthwise causal over ``[x | B | C]`` together; ``dt = softplus(dt +
dt_bias)`` and ``A = -exp(A_log)`` a head; head ``j`` uses the ``B`` and
``C`` of group ``j // (heads / groups)``; ``S_j <- exp(dt_j A_j) S_j + dt_j
x_j (x) B_g``, ``y_j = S_j C_g + D_j x_j`` on a float32 state ``(head_dim,
state)`` a head; ``y <- RMSNorm_group(y * silu(z))`` over a group's channels
(gate BEFORE the norm), then ``out_proj``.  Experts: the ``"sigmoid_bias"``
router, ``down(relu(up x)^2)`` (``expert_act`` ``"relu2"``: ``w1`` / ``w2``,
no gate) and a shared expert of the same form.  :func:`nemotron_h_spec`
reads the published keys; ``first`` / ``held`` give the share of the routed
experts held here.  A Mamba-2 layer has, under ``mamba2``:

=============  ==========================================================
``in_proj``    ``(d_model, 2 * d_inner + 2 * groups * state + heads)``,
               columns ``[z | x | B | C | dt]``
``conv_w``     ``(d_conv, d_inner + 2 * groups * state)`` over ``[x | B |
               C]``, tap ``k`` weighing the input ``d_conv - 1 - k`` tokens
               back; ``conv_b`` a channel
``dt_bias`` ``a_log`` ``d``   ``(heads,)``
``norm``       ``{"scale": (d_inner,)}``: the gated group norm's weight
``out_proj``   ``(d_inner, d_model)``
=============  ==========================================================

An expert layer has ``moe`` (``router (d_model, E)``, ``bias (E,)``, ``w1
(held, d_model, F)``, ``w2 (held, F, d_model)``) and ``shared`` ``w1 w2``.
The published expert width 1,856 is 14.5 x 128 lanes: the SERVED experts are
padded to whole lanes (``moe_ff_pad`` zero columns of ``w1`` and zero rows
of ``w2``, :func:`nemotron_h_layout`: ``relu(0)^2 = 0``, so the padded layer
gives the published layer's numbers), which is what lets both expert
products run the grouped kernel.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np


@dataclass(frozen=True)
class ModelSpec:
    """Hashable (it keys the jit memo) description of a decoder's layers."""
    n_layers: int
    d_model: int
    n_heads: int
    attention: str = "gqa"                  # "gqa" | "mla"
    n_kv_heads: int = 0                     # gqa
    head_dim: int = 0                       # gqa
    q_lora_rank: int = 0                    # mla, all five
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    layer_kinds: Tuple[str, ...] = ()       # "dense" | "moe" | "shortcut"
                                            # | "none" (a mixer ALONE)
    n_experts: int = 0                      # the router's columns
    top_k: int = 0
    moe_ff: int = 0
    n_shared: int = 0
    routed_scale: float = 1.0
    norm_topk: bool = True
    rms_eps: float = 1e-6
    rope_theta: Optional[float] = None
    mixers: Tuple[str, ...] = ()            # "attention" | "mamba" | "gdn"
                                            # | "cca" (set from cca_taps)
                                            # | "mamba2" | "none" (an FFN
                                            #   ALONE)
    d_inner: int = 0                        # mamba, all four
    d_state: int = 0
    d_conv: int = 0                         # mamba and gdn
    dt_rank: int = 0
    index_heads: int = 0                    # learned indexer (gqa), all three;
    index_dim: int = 0                      # 0 = none: every key is attended
    index_topk: int = 0
    qk_norm: bool = False                   # RMSNorm over each head of q and k
    router: str = "sigmoid_bias"            # | "softmax" (no selection bias)
                                            # | "softmax_bias" (all columns)
                                            # | "mlp" (an MLP, softmax_bias's
                                            #   choice and weights)
    gdn_k_heads: int = 0                    # Gated DeltaNet, all four (+ d_conv)
    gdn_v_heads: int = 0
    gdn_k_dim: int = 0
    gdn_v_dim: int = 0
    attn_gate: bool = False                 # gqa: o * sigmoid(gate), from q_proj
    rotary_dim: int = 0                     # gqa: RoPE columns; 0 = the head
    experts_held: int = 0                   # routed experts here; 0 = all
    expert_first: int = 0                   # the first of them
    shared_gate: bool = False               # shared expert * sigmoid(h w_g)
    eva_window: int = 0                     # EVA (gqa): positions a window,
    eva_chunk: int = 0                      # positions a summary row; 0 = none
    pred_heads: int = 0                     # output heads held (head 0 is read)
    zero_experts: int = 0                   # the router's LAST columns:
                                            # identity experts (no weights)
    hc_mult: int = 0                        # residual streams (mHC); 0 = the
                                            # plain residual x + f(norm(x))
    hc_sinkhorn_iters: int = 0              # sweeps that project H_res
    hc_eps: float = 1e-6                    # stream norm, Sinkhorn sums
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)   # H_res' logits
    rope_scaling: Tuple[float, ...] = ()    # YaRN: (factor, original max
                                            # positions, beta_fast,
                                            # beta_slow); () = none
    cca_taps: Tuple[int, ...] = ()          # CCA (gqa): taps of the depthwise
                                            # and of the grouped convolution
                                            # over [q ; k]; () = none
    router_width: int = 0                   # router "mlp": its hidden width
    res_scale: bool = False                 # s_r (x + b_r) + s_o (f + b_o)
    window: int = 0                         # gqa: keys a "window" layer's row
                                            # attends (itself among them)
    attn_kinds: Tuple[str, ...] = ()        # "full" | "window" a layer; () =
                                            # every layer full attention
    rope_factor: float = 1.0                # YaRN's factor on cos and sin of
                                            # the FULL layers (window: none)
    m2_heads: int = 0                       # Mamba-2, all five (+ d_conv):
    m2_head_dim: int = 0                    # heads and a head's channels,
    m2_groups: int = 0                      # groups that share B and C,
    m2_state: int = 0                       # a state's width (B's, C's),
    m2_chunk: int = 0                       # rows a chunk of the SSD form
    expert_act: str = "swiglu"              # | "relu2": down(relu(up x)^2),
                                            # TWO matrices an expert
    moe_ff_pad: int = 0                     # zero columns of w1 / rows of w2
                                            # the SERVED experts carry past
                                            # the published moe_ff (whole
                                            # lanes for the grouped product)

    def __post_init__(self):
        if self.attention not in ("gqa", "mla"):
            raise ValueError(f"unknown attention kind {self.attention!r}")
        if self.router not in ("sigmoid_bias", "softmax", "softmax_bias",
                               "mlp"):
            raise ValueError(f"unknown router kind {self.router!r}")
        if (self.router == "mlp") != (self.router_width > 0):
            raise ValueError('router="mlp" gives router_width (its hidden '
                             "width), and no other router has one")
        if self.cca_taps:
            # a CCA layer owns a layer of the page store AND of the lane
            # state store: what else keeps rows or a state a layer is
            # refused by name
            beside = {
                "latent attention": self.attention != "gqa",
                "an indexer": bool(self.index_topk),
                "EVA windows": bool(self.eva_window),
                "an output gate": self.attn_gate,
                "hyper-connections": bool(self.hc_mult),
                "a shortcut layer": "shortcut" in self.layer_kinds,
                "Mamba layers": "mamba" in self.mixers,
                "Gated DeltaNet layers": "gdn" in self.mixers}
            for other, there in beside.items():
                if there:
                    raise ValueError(
                        f"CCA beside {other} is not implemented: a CCA "
                        "layer keeps K/V pages and its convolutions' tails, "
                        "on plain GQA attention alone")
            if len(self.cca_taps) != 2 or min(self.cca_taps) < 2:
                raise ValueError(
                    f"cca_taps {self.cca_taps}: the taps (>= 2 each) of the "
                    "depthwise and of the grouped convolution")
            if set(self.mixers) - {"cca"}:
                raise ValueError(f"mixers {self.mixers}: with cca_taps every "
                                 "layer's mixer is cca")
            if self.n_kv_heads * self.head_dim % 2:
                raise ValueError("CCA cuts the value in two halves")
            object.__setattr__(self, "mixers", ("cca",) * self.n_layers)
        elif "cca" in self.mixers:
            raise ValueError("mixer cca comes with cca_taps")
        if self.res_scale and (self.hc_mult or "shortcut" in self.layer_kinds
                               or set(self.mixers) & {"mamba", "gdn"}):
            raise ValueError(
                "res_scale scales the plain residual around attention and a "
                "dense or expert FFN (no hyper-connections, no shortcut "
                "layer, no Mamba or Gated DeltaNet layer)")
        if self.index_topk or self.index_heads or self.index_dim:
            if min(self.index_heads, self.index_dim, self.index_topk) < 1:
                raise ValueError("an indexer gives index_heads, index_dim "
                                 "and index_topk")
            if self.attention != "gqa" or set(self.mixers or ()) - {
                    "attention"}:
                raise ValueError("an indexer selects keys of GQA attention "
                                 "on K/V pages only")
        kinds = self.layer_kinds or ("dense",) * self.n_layers
        if len(kinds) != self.n_layers or set(kinds) - {"dense", "moe",
                                                        "shortcut", "none"}:
            raise ValueError(f"layer_kinds {kinds} does not name a dense, "
                             f"moe or shortcut FFN (or none) for each of "
                             f"{self.n_layers} layers")
        object.__setattr__(self, "layer_kinds", tuple(kinds))
        if "shortcut" in kinds:
            # the expert block's output lands after the NEXT layer's FFN
            after = [k for i, k in enumerate(kinds + ("",))
                     if i and kinds[i - 1] == "shortcut"]
            if set(after) != {"dense"} or set(self.mixers or ()) - {
                    "attention"}:
                raise ValueError(
                    f"layer_kinds {kinds}: a shortcut layer is followed by "
                    "a dense layer that takes its expert block's output, "
                    "and every mixer is attention")
        mixers = self.mixers or ("attention",) * self.n_layers
        if (len(mixers) != self.n_layers
                or set(mixers) - {"attention", "mamba", "gdn", "cca",
                                  "mamba2", "none"}):
            raise ValueError(f"mixers {mixers} does not name attention, "
                             f"mamba, gdn, cca, mamba2 or none for each of "
                             f"{self.n_layers} layers")
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"unknown expert activation {self.expert_act!r}")
        if self.moe_ff_pad and (self.expert_act != "relu2"
                                or self.moe_ff_pad < 0):
            raise ValueError("moe_ff_pad pads relu2 experts alone: "
                             "relu(0)^2 = 0 makes a zero column exact")
        lone = "none" in mixers or "none" in kinds
        if lone or "mamba2" in mixers:
            # layers of ONE sublayer and the Mamba-2 state come together
            # (nemotron_h): what else keeps rows, a state or another
            # residual a layer is refused by name
            beside = {
                "latent attention": self.attention != "gqa",
                "an indexer": bool(self.index_topk),
                "EVA windows": bool(self.eva_window),
                "an output gate": self.attn_gate,
                "partial RoPE": bool(self.rotary_dim),
                "hyper-connections": bool(self.hc_mult),
                "a shortcut layer": "shortcut" in kinds,
                "residual scaling": self.res_scale,
                "an MLP router": self.router == "mlp",
                "CCA": bool(self.cca_taps),
                "window layers": bool(self.window or self.attn_kinds),
                "Mamba-1 or Gated DeltaNet layers":
                    bool(set(mixers) & {"mamba", "gdn"})}
            for other, there in beside.items():
                if there:
                    raise ValueError(
                        f"Mamba-2 layers and layers of one sublayer beside "
                        f"{other} are not implemented: a Mamba-2 state "
                        "beside plain GQA pages, dense and expert FFNs")
            if any(m == "none" and k == "none"
                   for m, k in zip(mixers, kinds)):
                raise ValueError("a layer with neither a mixer nor an FFN")
            if "mamba2" not in mixers or "attention" not in mixers:
                raise ValueError(
                    "layers of one sublayer are served for a model with "
                    "Mamba-2 layers beside GQA attention layers on K/V "
                    "pages (a lane state of ONE kind)")
            if min(self.m2_heads, self.m2_head_dim, self.m2_groups,
                   self.m2_state, self.m2_chunk, self.d_conv - 1) < 1 or (
                       self.m2_heads % self.m2_groups):
                raise ValueError(
                    "a spec with Mamba-2 layers gives m2_heads (a multiple "
                    "of m2_groups), m2_head_dim, m2_groups, m2_state, "
                    "m2_chunk and d_conv (>= 2)")
        if "gdn" in mixers:
            if (self.attention != "gqa" or "attention" not in mixers
                    or "mamba" in mixers):
                raise ValueError("Gated DeltaNet layers are served beside "
                                 "GQA attention layers on K/V pages only, "
                                 "and a lane state is of one kind")
            if min(self.gdn_k_heads, self.gdn_v_heads, self.gdn_k_dim,
                   self.gdn_v_dim, self.d_conv - 1) < 1 or (
                       self.gdn_v_heads % self.gdn_k_heads):
                raise ValueError(
                    "a spec with Gated DeltaNet layers gives gdn_k_heads, "
                    "gdn_v_heads (a multiple of them), gdn_k_dim, gdn_v_dim "
                    "and d_conv (>= 2)")
        if (self.attn_gate or self.rotary_dim) and (
                self.attention != "gqa" or self.index_topk):
            raise ValueError("attn_gate and rotary_dim belong to plain GQA "
                             "attention")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} is not an even "
                             f"part of head_dim {self.head_dim}")
        if self.eva_window or self.eva_chunk:
            w, c = self.eva_window, self.eva_chunk
            if min(w, c) < 1 or w % c or (w // c) % c:
                raise ValueError(
                    f"eva_window {w} / eva_chunk {c}: a window is whole "
                    "chunks and its summaries are whole chunks (pages) too")
            if (self.attention != "gqa" or self.index_topk or self.attn_gate
                    or set(self.mixers or ()) - {"attention"}):
                raise ValueError("EVA windows belong to plain GQA attention "
                                 "on K/V pages, every layer")
        if not 0 <= self.zero_experts <= max(self.n_experts - 1, 0):
            raise ValueError(
                f"zero_experts {self.zero_experts} are not the last columns "
                f"of a router of {self.n_experts} that has an FFN expert")
        held = self.experts_held or self.ffn_experts
        if not 0 <= self.expert_first <= self.ffn_experts - held:
            raise ValueError(
                f"experts {self.expert_first} .. {self.expert_first + held} "
                f"are not a share of the router's {self.ffn_experts}")
        if self.shared_gate and not self.n_shared:
            raise ValueError("shared_gate without a shared expert")
        if self.hc_mult:
            if (self.hc_mult < 2 or self.hc_sinkhorn_iters < 1
                    or "shortcut" in kinds
                    or set(self.mixers or ()) - {"attention"}):
                raise ValueError(
                    "hyper-connections give hc_mult (>= 2) streams and "
                    "hc_sinkhorn_iters (>= 1) around attention and a dense "
                    "or expert FFN (no shortcut layer, no lane-state mixer)")
        if self.rope_scaling and (len(self.rope_scaling) != 4
                                  or (self.attention != "mla"
                                      and not self.window)):
            raise ValueError("rope_scaling is YaRN's (factor, original max "
                             "positions, beta_fast, beta_slow) on the rope "
                             "columns of latent attention, or on the full "
                             "layers of a model with window layers")
        if self.window or self.attn_kinds:
            # window layers beside full ones: two groups of the page store
            # (``page_groups``), a table a lane each.  What else keeps rows,
            # a state or another mask a layer is refused by name
            beside = {
                "latent attention": self.attention != "gqa",
                "an indexer": bool(self.index_topk),
                "EVA windows": bool(self.eva_window),
                "an output gate": self.attn_gate,
                "partial RoPE": bool(self.rotary_dim),
                "hyper-connections": bool(self.hc_mult),
                "a shortcut layer": "shortcut" in self.layer_kinds,
                "a lane state (Mamba, Gated DeltaNet, CCA layers)":
                    bool(set(mixers) - {"attention"})}
            for other, there in beside.items():
                if there:
                    raise ValueError(
                        f"window layers beside {other} are not implemented: "
                        "a sliding window is a lower bound on plain GQA "
                        "attention over K/V pages")
            kinds_a = tuple(self.attn_kinds)
            if (len(kinds_a) != self.n_layers
                    or set(kinds_a) - {"full", "window"}):
                raise ValueError(f"attn_kinds {kinds_a} does not name full "
                                 f"or window for each of {self.n_layers} "
                                 "layers")
            if ("window" in kinds_a) != (self.window > 0):
                raise ValueError("window (the keys a row attends) comes with "
                                 "window layers in attn_kinds, and they "
                                 "with it")
            if "full" not in kinds_a:
                raise ValueError(
                    "window layers alone are not implemented: the page "
                    "store's first group is the full layers', which "
                    "admission and the gauges read")
            object.__setattr__(self, "attn_kinds", kinds_a)
        if self.rope_factor != 1.0 and not self.rope_scaling:
            raise ValueError("rope_factor is YaRN's factor on cos and sin: "
                             "it comes with rope_scaling")
        if "mamba" in mixers:
            if self.attention != "gqa" or "attention" not in mixers:
                raise ValueError("Mamba layers are served beside GQA "
                                 "attention layers on K/V pages only")
            if min(self.d_inner, self.d_state, self.d_conv - 1,
                   self.dt_rank) < 1:
                raise ValueError("a spec with Mamba layers gives d_inner, "
                                 "d_state, d_conv (>= 2) and dt_rank")
        object.__setattr__(self, "mixers", tuple(mixers))

    @property
    def cache_entry(self) -> str:
        """What a token leaves in the page store: ``"kv"``, ``"latent"`` or
        ``"kv_index"``."""
        if self.attention == "mla":
            return "latent"
        return "kv_index" if self.index_topk else "kv"

    @property
    def eva_summaries(self) -> int:
        """Summary rows a finished window leaves (0 without EVA)."""
        return self.eva_window // self.eva_chunk if self.eva_window else 0

    def cache_row(self, pos):
        """The row of a lane's page table that holds position ``pos`` (an
        integer or an array of them): ``pos`` itself, or with EVA windows
        ``(pos // W) * S + pos % W``: every earlier window is ``S`` summary
        rows, the position's own window follows them whole."""
        if not self.eva_window:
            return pos
        return pos - (pos // self.eva_window) * (
            self.eva_window - self.eva_summaries)

    def cache_rows_peak(self, n: int, done: int = 0) -> int:
        """The most rows a lane holds from now until it has taken in ``n``
        positions, its first ``done`` windows compacted already: ``n``
        itself, or with EVA windows the larger of the rows of the final
        state and, where a window is still to finish on the way, that
        window whole beside the summaries before it."""
        if not self.eva_window or n <= 0:
            return max(n, 0)
        w, s = self.eva_window, self.eva_summaries
        last = max((n - 1) // w, done)
        rows = last * s + n - last * w
        return max(rows, (last - 1) * s + w) if last > done else rows

    @property
    def latent_width(self) -> int:
        """Values of a latent row: ``[c_kv ; k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def rope_inv_freq(self) -> Optional[np.ndarray]:
        """The inverse frequencies RoPE turns the ``qk_rope_head_dim``
        columns by (latent attention; the ``head_dim`` columns of a GQA
        model's FULL layers, whose window layers keep ``theta^(-2j / d)``)
        where ``rope_scaling`` changes them (float32, ``(rope /
        2,)``), else None: ``theta^(-2j / d)`` it is.  YaRN: pair ``j``
        keeps its frequency where it turns more than ``beta_fast`` times
        within the original context, has it divided by ``factor`` where it
        turns fewer than ``beta_slow`` times, and a linear ramp between."""
        if not self.rope_scaling:
            return None
        factor, original, fast, slow = self.rope_scaling
        d = (self.qk_rope_head_dim if self.attention == "mla"
             else self.head_dim)
        theta = self.rope_theta

        def corr(turns):
            return d * np.log(original / (2 * np.pi * turns)) / (
                2 * np.log(theta))
        low = int(np.clip(np.floor(corr(fast)), 0, d // 2 - 1))
        high = int(np.clip(np.ceil(corr(slow)), 0, d // 2 - 1))
        j = np.arange(d // 2, dtype=np.float64)
        ramp = np.clip((j - low) / max(high - low, 1e-3), 0, 1)
        return (theta ** (-2 * j / d)
                * ((1 - ramp) + ramp / factor)).astype(np.float32)

    @property
    def ffn_experts(self) -> int:
        """The router's columns that are FFN experts: all of them but the
        last ``zero_experts``."""
        return self.n_experts - self.zero_experts

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        """The layers that run an expert block (``"moe"``, ``"shortcut"``)."""
        return tuple(i for i, k in enumerate(self.layer_kinds)
                     if k not in ("dense", "none"))

    @property
    def moe_ff_served(self) -> int:
        """The width of the experts as SERVED: the published ``moe_ff`` and
        the zero columns behind it (:func:`nemotron_h_layout`)."""
        return self.moe_ff + self.moe_ff_pad

    @property
    def m2_conv_dim(self) -> int:
        """Channels of a Mamba-2 layer's convolution: ``[x | B | C]``."""
        return (self.m2_heads * self.m2_head_dim
                + 2 * self.m2_groups * self.m2_state)

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.mixers) if k == "mamba")

    @property
    def state_kind(self) -> Optional[str]:
        """The kind of per-lane state the model's layers keep beside the
        pages: ``"mamba"``, ``"gdn"``, ``"cca"``, ``"mamba2"`` or None."""
        for kind in ("mamba", "gdn", "cca", "mamba2"):
            if kind in self.mixers:
                return kind
        return None

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that own a layer of the lane-state store."""
        return tuple(i for i, k in enumerate(self.mixers)
                     if k not in ("attention", "none"))

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        """The layers that own a layer of the page store (a CCA layer owns
        one of each store)."""
        return tuple(i for i, k in enumerate(self.mixers)
                     if k in ("attention", "cca"))

    def store_layer(self, layer: int) -> int:
        """Layer ``layer``'s index in the store of its mixer's kind: the
        page store's layer axis for an attention layer, the lane-state
        store's for a Mamba or Gated DeltaNet layer (``layer`` itself where
        every mixer is attention, or CCA, which owns that layer of both).
        With window layers (``attn_kinds``) an attention layer's index in
        the page store's GROUP of its kind (:meth:`page_groups`)."""
        if self.attn_kinds:
            return self.attn_kinds[:layer].count(self.attn_kinds[layer])
        return self.mixers[:layer].count(self.mixers[layer])

    def layer_window(self, layer: int) -> int:
        """The keys a row of ``layer`` attends at most, itself among them
        (key ``j`` is seen from position ``i`` iff ``i - window < j <=
        i``); 0 on a full layer: every key at or before the row."""
        return (self.window if self.attn_kinds
                and self.attn_kinds[layer] == "window" else 0)

    @property
    def page_groups(self) -> Tuple[Tuple[str, int], ...]:
        """The page store's layer groups, ``(name, layers)`` each: one,
        ``"full"``, of every layer that owns pages, or with window layers
        two, ``"full"`` and ``"window"``, each with its own array, free
        extents, reference counts and table a lane: a window layer's pages
        behind its window go back to its group while the full layers keep
        theirs."""
        if not self.attn_kinds:
            return (("full", len(self.attention_layers)),)
        return tuple((kind, self.attn_kinds.count(kind))
                     for kind in ("full", "window"))


def dense_spec(d_model: int, n_heads: int, n_layers: int,
               n_kv_heads: Optional[int] = None,
               rope_theta: Optional[float] = None) -> ModelSpec:
    """The decoder the engine always served, with its constants."""
    return ModelSpec(n_layers=n_layers, d_model=d_model, n_heads=n_heads,
                     n_kv_heads=n_kv_heads or n_heads,
                     head_dim=d_model // n_heads, rope_theta=rope_theta)


def glm4_moe_lite_spec(config: Dict[str, Any]) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type``
    ``glm4_moe_lite``).  Refuses what the layer block does not compute."""
    if int(config.get("n_group", 1)) != 1 or int(config.get("topk_group",
                                                            1)) != 1:
        raise ValueError("group-limited routing (n_group/topk_group > 1) is "
                         "not implemented")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if float(config.get("partial_rotary_factor", 1)) != 1:
        raise ValueError("partial_rotary_factor != 1 is not implemented")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not implemented")
    n_layers = int(config["num_hidden_layers"])
    n_dense = int(config["first_k_dense_replace"])
    return ModelSpec(
        n_layers=n_layers, d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]), attention="mla",
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        layer_kinds=tuple("dense" if i < n_dense else "moe"
                          for i in range(n_layers)),
        n_experts=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        moe_ff=int(config["moe_intermediate_size"]),
        n_shared=int(config["n_shared_experts"]),
        routed_scale=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]),
        rms_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]))


def jamba_spec(config: Dict[str, Any]) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type`` ``jamba``).
    Layer ``i`` is attention iff ``i % attn_layer_period ==
    attn_layer_offset``, else Mamba.  Refuses what the layer block does not
    compute."""
    if int(config.get("num_experts", 1)) > 1:
        raise ValueError("num_experts > 1 (Jamba's expert layers) is not "
                         "implemented")
    if config.get("sliding_window") is not None:
        raise ValueError("sliding_window is not implemented")
    if config.get("mamba_proj_bias"):
        raise ValueError("mamba_proj_bias is not implemented")
    if not config.get("mamba_conv_bias", True):
        raise ValueError("mamba_conv_bias false is not implemented")
    n_layers = int(config["num_hidden_layers"])
    d_model, n_heads = int(config["hidden_size"]), int(config[
        "num_attention_heads"])
    period, offset = (int(config["attn_layer_period"]),
                      int(config["attn_layer_offset"]))
    return ModelSpec(
        n_layers=n_layers, d_model=d_model, n_heads=n_heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=d_model // n_heads,
        rms_eps=float(config["rms_norm_eps"]), rope_theta=None,
        mixers=tuple("attention" if i % period == offset else "mamba"
                     for i in range(n_layers)),
        d_inner=int(config["mamba_expand"]) * d_model,
        d_state=int(config["mamba_d_state"]),
        d_conv=int(config["mamba_d_conv"]),
        dt_rank=int(config["mamba_dt_rank"]))


def keye_vl2_spec(config: Dict[str, Any]) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type`` ``KeyeVL2``)
    and its ``sa_config``.  Text positions only: with one position on all
    three axes of ``mrope_section`` M-RoPE is RoPE.  Refuses what the layer
    block does not compute."""
    if config.get("mlp_only_layers"):
        raise ValueError("mlp_only_layers is not implemented (every layer "
                         "is an expert layer)")
    if int(config.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("decoder_sparse_step != 1 is not implemented")
    if config.get("use_sliding_window"):
        raise ValueError("use_sliding_window is not implemented")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not implemented")
    scaling = config.get("rope_scaling") or {}
    kind = scaling.get("rope_type", scaling.get("type", "default"))
    if kind not in ("default", "mrope"):
        raise ValueError(f"rope_scaling type {kind!r} is not implemented")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false is not implemented (the "
                         "softmax router renormalises over the chosen k)")
    sa = config["sa_config"]
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("indexer_num_kv_heads != 1 is not implemented (one "
                         "index key a token)")
    n_layers = int(config["num_hidden_layers"])
    return ModelSpec(
        n_layers=n_layers, d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        layer_kinds=("moe",) * n_layers,
        n_experts=int(config["num_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        moe_ff=int(config["moe_intermediate_size"]), n_shared=0,
        router="softmax", qk_norm=True,
        rms_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        index_heads=int(sa["indexer_num_heads"]),
        index_dim=int(sa["indexer_head_dim"]),
        index_topk=int(sa["topk"]))


def qwen3_next_spec(config: Dict[str, Any], first: int = 0,
                    held: Optional[int] = None) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type``
    ``qwen3_next``).  Layer ``i`` is attention iff ``(i + 1) %
    full_attention_interval == 0``, else Gated DeltaNet; every layer's FFN
    is an expert layer.  ``first`` / ``held``: the contiguous share of the
    ``num_experts`` routed experts this device holds (all of them by
    default); the router keeps every column.  Refuses what the layer block
    does not compute."""
    if config.get("mlp_only_layers"):
        raise ValueError("mlp_only_layers is not implemented (every layer "
                         "is an expert layer)")
    if int(config.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("decoder_sparse_step != 1 is not implemented")
    if config.get("use_sliding_window"):
        raise ValueError("use_sliding_window is not implemented")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not implemented")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false is not implemented (the "
                         "softmax router renormalises over the chosen k)")
    moe_ff = int(config["moe_intermediate_size"])
    shared = int(config["shared_expert_intermediate_size"])
    if shared % moe_ff:
        raise ValueError(f"shared_expert_intermediate_size {shared} is not "
                         f"a multiple of moe_intermediate_size {moe_ff}")
    n_layers = int(config["num_hidden_layers"])
    period = int(config["full_attention_interval"])
    head_dim = int(config["head_dim"])
    n_experts = int(config["num_experts"])
    return ModelSpec(
        n_layers=n_layers, d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=head_dim,
        layer_kinds=("moe",) * n_layers, n_experts=n_experts,
        top_k=int(config["num_experts_per_tok"]), moe_ff=moe_ff,
        n_shared=shared // moe_ff, shared_gate=True, router="softmax",
        experts_held=n_experts if held is None else int(held),
        expert_first=int(first), qk_norm=True, attn_gate=True,
        rotary_dim=int(head_dim * float(config.get("partial_rotary_factor",
                                                   1))),
        rms_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        mixers=tuple("attention" if (i + 1) % period == 0 else "gdn"
                     for i in range(n_layers)),
        d_conv=int(config["linear_conv_kernel_dim"]),
        gdn_k_heads=int(config["linear_num_key_heads"]),
        gdn_v_heads=int(config["linear_num_value_heads"]),
        gdn_k_dim=int(config["linear_key_head_dim"]),
        gdn_v_dim=int(config["linear_value_head_dim"]))


def evabyte_spec(config: Dict[str, Any]) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type`` ``evabyte``,
    ``attention_class`` ``eva``).  Refuses what the layer block does not
    compute."""
    if config.get("attention_class", "eva") != "eva":
        raise ValueError(f"attention_class {config['attention_class']!r} is "
                         "not implemented (eva alone)")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not implemented")
    if config.get("tie_word_embeddings"):
        raise ValueError("tie_word_embeddings is not implemented (the head "
                         "is num_pred_heads x vocab_size rows of its own)")
    if not config.get("norm_add_unit_offset", True):
        raise ValueError("norm_add_unit_offset false is not implemented "
                         "(norm scales are loaded as 1 + w)")
    if config.get("num_chunks") is not None:
        raise ValueError("num_chunks is not implemented (chunk_size alone "
                         "sizes a summary)")
    window, chunk = int(config["window_size"]), int(config["chunk_size"])
    if window % chunk:
        raise ValueError(f"window_size {window} is not whole chunks of "
                         f"chunk_size {chunk}")
    d_model, n_heads = (int(config["hidden_size"]),
                        int(config["num_attention_heads"]))
    return ModelSpec(
        n_layers=int(config["num_hidden_layers"]), d_model=d_model,
        n_heads=n_heads, n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=d_model // n_heads, rms_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]), eva_window=window,
        eva_chunk=chunk, pred_heads=int(config.get("num_pred_heads", 1)))


def longcat_flash_spec(config: Dict[str, Any], first: int = 0,
                       held: Optional[int] = None) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type``
    ``longcat_flash``).  A published layer is two engine layers, kinds
    ``("shortcut", "dense")``, each with its own layer of the latent page
    store.  ``first`` / ``held``: the contiguous share of the
    ``n_routed_experts`` FFN experts this device holds (all of them by
    default); the router keeps every column, the ``zero_expert_num``
    identity experts behind them.  Refuses what the layer block does not
    compute."""
    if config.get("zero_expert_type", "identity") != "identity":
        raise ValueError(f"zero_expert_type {config['zero_expert_type']!r} "
                         "is not implemented (identity alone)")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not implemented")
    if config.get("router_bias"):
        raise ValueError("router_bias is not implemented (the router is a "
                         "matrix; e_score_correction_bias is the selection "
                         "bias)")
    if config.get("norm_topk_prob"):
        raise ValueError("norm_topk_prob true is not implemented (a chosen "
                         "column weighs routed_scaling_factor * s)")
    if config.get("attention_method", "MLA") != "MLA":
        raise ValueError(f"attention_method {config['attention_method']!r} "
                         "is not implemented (MLA alone)")
    n_ffn, n_zero = (int(config["n_routed_experts"]),
                     int(config.get("zero_expert_num", 0)))
    return ModelSpec(
        n_layers=2 * int(config["num_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]), attention="mla",
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        layer_kinds=("shortcut", "dense") * int(config["num_layers"]),
        n_experts=n_ffn + n_zero, zero_experts=n_zero,
        top_k=int(config["moe_topk"]),
        moe_ff=int(config["expert_ffn_hidden_size"]), n_shared=0,
        router="softmax_bias", norm_topk=False,
        routed_scale=float(config["routed_scaling_factor"]),
        experts_held=n_ffn if held is None else int(held),
        expert_first=int(first), rms_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]))


def xing4_spec(config: Dict[str, Any]) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type`` ``xing4_0``):
    :func:`glm4_moe_lite_spec`'s layer inside ``hc_mult`` hyper-connected
    residual streams, RoPE under YaRN.  Refuses what the layer block does
    not compute."""
    scaling = config.get("rope_scaling") or {}
    kind = scaling.get("rope_type", scaling.get("type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r} is not implemented "
                         "(yarn alone)")
    if float(scaling.get("mscale", 1)) != float(scaling.get("mscale_all_dim",
                                                            0)):
        raise ValueError("rope_scaling mscale != mscale_all_dim (a factor "
                         "on cos and sin) is not implemented")
    if int(config.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq != 1 is not implemented")
    if int(config.get("ep_size", 1)) != 1:
        raise ValueError("ep_size != 1 is not implemented")
    if int(config.get("hc_mult", 0)) < 2:
        raise ValueError("hc_mult < 2 is not implemented (xing4_0 is served "
                         "with its residual streams)")
    base = glm4_moe_lite_spec(dict(config, rope_scaling=None))
    return dataclasses.replace(
        base, hc_mult=int(config["hc_mult"]),
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        hc_clamp=(float(config["mhc_h_res_clamp_min"]),
                  float(config["mhc_h_res_clamp_max"])),
        rope_scaling=(float(scaling["factor"]),
                      float(scaling["original_max_position_embeddings"]),
                      float(scaling["beta_fast"]),
                      float(scaling["beta_slow"])))


def zaya_spec(config: Dict[str, Any]) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type`` ``zaya``):
    every layer (``layer_types`` all ``"hybrid"``) compressed convolutional
    attention and ``num_experts`` experts behind the MLP router, whose last
    column is the skip column (the family's ``zaya_use_mod``; depth
    averaging its ``zaya_use_eda``; residual scaling its
    ``scale_residual_merge``: the published config of this model has no key
    for the three, the configuration file's ``assumed`` says so).  Refuses
    what the layer block does not compute."""
    kinds = set(config.get("layer_types") or ["hybrid"])
    if kinds != {"hybrid"}:
        raise ValueError(f"layer_types {sorted(kinds)} is not implemented "
                         "(hybrid alone: no sliding-window layer)")
    if config.get("sliding_window") is not None:
        raise ValueError("sliding_window is not implemented")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not implemented")
    if config.get("lm_head_bias"):
        raise ValueError("lm_head_bias is not implemented")
    if not config.get("tie_word_embeddings", True):
        raise ValueError("tie_word_embeddings false is not implemented (the "
                         "head is the embedding)")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r} is not "
                         "implemented (SwiGLU experts)")
    rope = (config.get("rope_parameters") or {}).get("hybrid") or config
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not "
                         "implemented")
    n_layers, head_dim = (int(config["num_hidden_layers"]),
                          int(config["head_dim"]))
    return ModelSpec(
        n_layers=n_layers, d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=head_dim,
        rotary_dim=int(head_dim * float(rope.get("partial_rotary_factor",
                                                 1))),
        rope_theta=float(rope["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        cca_taps=(int(config["cca_time0"]), int(config["cca_time1"])),
        res_scale=True,
        layer_kinds=("moe",) * n_layers,
        n_experts=int(config["num_experts"]) + 1, zero_experts=1,
        top_k=int(config["num_experts_per_tok"]),
        moe_ff=int(config["moe_intermediate_size"]), n_shared=0,
        router="mlp", router_width=int(config["router_hidden_size"]),
        norm_topk=False)


def mellum_spec(config: Dict[str, Any]) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type`` ``mellum``):
    ``layer_types`` names each layer ``sliding_attention`` (``sliding_window``
    keys, plain RoPE: ``rope_parameters.sliding_attention``) or
    ``full_attention`` (YaRN: ``rope_parameters.full_attention``, its
    ``attention_factor`` on cos and sin), GQA with an RMSNorm over each head
    of q and k (the family's, ``assumed`` in the configuration file), every
    layer followed by ``num_experts`` softmax-routed experts renormalised
    over the chosen ``num_experts_per_tok``, no shared expert, an untied
    head.  Refuses what the layer block does not compute."""
    n_layers = int(config["num_hidden_layers"])
    # (a configuration cut in depth keeps the published lists whole: the
    # layers served are their first ``num_hidden_layers`` entries)
    types = list(config.get("layer_types")
                 or ["full_attention"] * n_layers)[:n_layers]
    known = {"sliding_attention": "window", "full_attention": "full"}
    if len(types) != n_layers or set(types) - set(known):
        raise ValueError(f"layer_types {sorted(set(types) - set(known))} is "
                         "not implemented (sliding_attention and "
                         f"full_attention, one for each of {n_layers} "
                         "layers)")
    ffn = set((config.get("mlp_layer_types") or ["sparse"])[:n_layers])
    if ffn != {"sparse"}:
        raise ValueError(f"mlp_layer_types {sorted(ffn - {'sparse'})} is not "
                         "implemented (every layer is an expert layer: "
                         "sparse alone)")
    if config.get("tie_word_embeddings"):
        raise ValueError("tie_word_embeddings true is not implemented (the "
                         "head is a matrix of its own)")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not implemented")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r} is not "
                         "implemented (SwiGLU experts)")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false is not implemented (the "
                         "softmax router renormalises over the chosen k)")
    kinds = tuple(known[t] for t in types)
    windowed = "window" in kinds
    if windowed and not config.get("use_sliding_window", True):
        raise ValueError("use_sliding_window false beside sliding_attention "
                         "layers is not implemented")
    if windowed and int(config.get("max_window_layers") or 0):
        raise ValueError("max_window_layers != 0 is not implemented "
                         "(layer_types says which layers slide)")
    rope = config.get("rope_parameters") or {}
    slide = rope.get("sliding_attention") or {}
    full = rope.get("full_attention") or {}
    if windowed and slide.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters.sliding_attention rope_type "
                         f"{slide['rope_type']!r} is not implemented "
                         "(default alone)")
    kind = full.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"rope_parameters.full_attention rope_type {kind!r} "
                         "is not implemented (default and yarn)")
    theta = float(full.get("rope_theta", config.get("rope_theta", 0)) or 0)
    if windowed and float(slide.get("rope_theta", theta)) != theta:
        raise ValueError("rope_theta that differs between the layer kinds "
                         "is not implemented")
    scaling, factor = (), 1.0
    if kind == "yarn":
        if full.get("truncate") is False:
            raise ValueError("rope_parameters.full_attention truncate false "
                             "is not implemented")
        s = float(full["factor"])
        scaling = (s, float(full["original_max_position_embeddings"]),
                   float(full.get("beta_fast", 32)),
                   float(full.get("beta_slow", 1)))
        # Hugging Face's default where the config gives none: 0.1 ln s + 1
        factor = float(full.get("attention_factor")
                       or 0.1 * np.log(s) + 1.0)
    return ModelSpec(
        n_layers=n_layers, d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        layer_kinds=("moe",) * n_layers,
        n_experts=int(config["num_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        moe_ff=int(config["moe_intermediate_size"]), n_shared=0,
        router="softmax", qk_norm=True,
        rms_eps=float(config["rms_norm_eps"]), rope_theta=theta,
        window=int(config["sliding_window"]) if windowed else 0,
        attn_kinds=kinds if windowed else (),
        rope_scaling=scaling, rope_factor=factor)


def nemotron_h_spec(config: Dict[str, Any], first: int = 0,
                    held: Optional[int] = None) -> ModelSpec:
    """From the published ``config.json`` keys (``model_type``
    ``nemotron_h``).  Layer ``i`` is what letter ``i`` of
    ``hybrid_override_pattern`` says, ONE sublayer: ``M`` Mamba-2, ``*``
    attention (no positional rotation: ``rope_theta`` and
    ``partial_rotary_factor`` are read by nothing), ``E`` the expert block.
    ``d_inner`` is ``mamba_num_heads x mamba_head_dim`` (``expand`` is read
    by nothing).  ``first`` / ``held``: the contiguous share of the
    ``n_routed_experts`` routed experts this device holds (all of them by
    default); the router keeps every column.  The served experts are padded
    to whole lanes (``moe_ff_pad``).  Refuses what the layer block does not
    compute."""
    n_layers = int(config["num_hidden_layers"])
    # (a configuration cut in depth keeps the published pattern whole: the
    # layers served are its first ``num_hidden_layers`` letters)
    pattern = str(config["hybrid_override_pattern"])[:n_layers]
    if len(pattern) != n_layers or set(pattern) - set("ME*"):
        raise ValueError(
            f"hybrid_override_pattern letters "
            f"{sorted(set(pattern) - set('ME*'))} are not implemented (M "
            f"Mamba-2, E experts, * attention, one for each of {n_layers} "
            "layers; '-' dense MLP layers are not)")
    if int(config.get("n_group", 1)) != 1 or int(config.get("topk_group",
                                                            1)) != 1:
        raise ValueError("group-limited routing (n_group/topk_group > 1) is "
                         "not implemented")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias",
                "tie_word_embeddings", "residual_in_fp32"):
        if config.get(key):
            raise ValueError(f"{key} true is not implemented")
    if not config.get("use_conv_bias", True):
        raise ValueError("use_conv_bias false is not implemented")
    if config.get("sliding_window") is not None:
        raise ValueError("sliding_window is not implemented")
    if config.get("mlp_hidden_act", "relu2") != "relu2" or config.get(
            "mamba_hidden_act", "silu") != "silu":
        raise ValueError("mlp_hidden_act relu2 and mamba_hidden_act silu "
                         "alone are implemented")
    moe_ff = int(config["moe_intermediate_size"])
    shared = int(config["moe_shared_expert_intermediate_size"]) * int(
        config.get("n_shared_experts", 1))
    if shared % moe_ff:
        raise ValueError(f"the shared experts' width {shared} is not a "
                         f"multiple of moe_intermediate_size {moe_ff}")
    n_experts = int(config["n_routed_experts"])
    return ModelSpec(
        n_layers=n_layers, d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]), rope_theta=None,
        rms_eps=float(config["layer_norm_epsilon"]),
        mixers=tuple({"M": "mamba2", "*": "attention", "E": "none"}[c]
                     for c in pattern),
        layer_kinds=tuple("moe" if c == "E" else "none" for c in pattern),
        d_conv=int(config["conv_kernel"]),
        m2_heads=int(config["mamba_num_heads"]),
        m2_head_dim=int(config["mamba_head_dim"]),
        m2_groups=int(config["n_groups"]),
        m2_state=int(config["ssm_state_size"]),
        m2_chunk=int(config["chunk_size"]),
        n_experts=n_experts, top_k=int(config["num_experts_per_tok"]),
        moe_ff=moe_ff, moe_ff_pad=-moe_ff % 128, n_shared=shared // moe_ff,
        expert_act="relu2", router="sigmoid_bias",
        routed_scale=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]),
        experts_held=n_experts if held is None else int(held),
        expert_first=int(first))


def nemotron_h_layout(w1, w2, spec: ModelSpec):
    """The published ``up_proj`` / ``down_proj`` of the experts held,
    transposed (``w1 (E, d_model, moe_ff)``, ``w2 (E, moe_ff, d_model)``),
    as the served ``(w1, w2)``: ``moe_ff_pad`` zero columns behind ``w1``'s
    and as many zero rows behind ``w2``'s, so that the width is whole
    128-lane tiles and both products run the grouped kernel.  Exact: a zero
    column gives ``relu(0)^2 = 0``, which a zero row of ``w2`` meets."""
    pad = spec.moe_ff_pad
    if not pad:
        return w1, w2
    if isinstance(w1, np.ndarray):
        lib = np
    else:
        import jax.numpy as lib
    return (lib.pad(w1, ((0, 0), (0, 0), (0, pad))),
            lib.pad(w2, ((0, 0), (0, pad), (0, 0))))


def mla_scales(config: Dict[str, Any]) -> Tuple[float, float]:
    """``(query factor, latent factor)`` that a config puts on latent
    attention and the program folds into its matrices at load time:
    ``(hidden_size / q_lora_rank)^0.5`` where ``mla_scale_q_lora``,
    ``(hidden_size / kv_lora_rank)^0.5`` where ``mla_scale_kv_lora``
    (``longcat_flash``), else 1; under YaRN (``rope_scaling``) the query
    factor also carries what YaRN multiplies the softmax scale by,
    ``mscale(factor, mscale_all_dim)^2`` with ``mscale(s, m) = 0.1 m ln s +
    1`` (``xing4_0``: 1.4159^2 at factor 64)."""
    d = float(config["hidden_size"])
    q = ((d / int(config["q_lora_rank"])) ** 0.5
         if config.get("mla_scale_q_lora") else 1.0)
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type")) == "yarn":
        s, m = float(scaling["factor"]), float(scaling.get("mscale_all_dim",
                                                           0))
        if s > 1 and m:
            q *= (0.1 * m * np.log(s) + 1.0) ** 2
    return (float(q), (d / int(config["kv_lora_rank"])) ** 0.5
            if config.get("mla_scale_kv_lora") else 1.0)


def scale_queries(params: Dict[str, Any], spec: ModelSpec,
                  q_scale: float) -> Dict[str, Any]:
    """``params`` (a tree in the served layout, numpy or jax arrays) with
    every layer's ``wq_b`` times ``q_scale`` (:func:`mla_scales`): what
    turns a tree whose ``wq_b`` is the published ``q_b_proj`` into the one
    an ``xing4_0`` engine is handed.  The product is float32 (a bf16 matrix
    times a bf16 2.0047 would be times 2)."""
    out = dict(params)
    for i in range(spec.n_layers):
        p = params[f"layer{i}"]
        out[f"layer{i}"] = dict(
            p, wq_b=(p["wq_b"].astype("float32") * q_scale).astype(
                p["wq_b"].dtype))
    return out


def longcat_flash_layout(wq_b, wkv_a, kv_b, spec: ModelSpec,
                         q_scale: float = 1.0, kv_scale: float = 1.0):
    """One attention's published ``q_b_proj (q_lora_rank, H * (nope +
    rope))``, ``kv_a_proj_with_mqa (d_model, kv_lora_rank + rope)`` and
    ``kv_b_proj`` (all transposed: inputs by outputs) as the served
    ``(wq_b, wkv_a, w_uk, w_uv)``, float32 (numpy or jax arrays): the rope
    columns of a query head and of the shared key reordered ``[even | odd]``
    (the published RoPE turns interleaved pairs ``(2j, 2j + 1)``, the
    engine's rotate-half pairs ``(j, j + rope / 2)``: the same rotation of
    the same pair), ``wq_b`` times ``q_scale``, the two halves of
    ``kv_b_proj`` (:func:`split_kv_b`) times ``kv_scale``
    (:func:`mla_scales`)."""
    nope, rope = spec.qk_nope_head_dim, spec.qk_rope_head_dim
    turn = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    head = np.concatenate([np.arange(nope), nope + turn])
    q_cols = (np.arange(spec.n_heads)[:, None] * (nope + rope)
              + head[None, :]).reshape(-1)
    kv_cols = np.concatenate([np.arange(spec.kv_lora_rank),
                              spec.kv_lora_rank + turn])
    w_uk, w_uv = split_kv_b(kv_b, spec)
    return (wq_b[:, q_cols] * q_scale, wkv_a[:, kv_cols], w_uk * kv_scale,
            w_uv * kv_scale)


def split_pred_heads(head, spec: ModelSpec):
    """A published output head ``(d_model, num_pred_heads * vocab)`` (the
    transposed ``lm_head.weight``, prediction head ``j`` in columns ``[j *
    vocab, (j + 1) * vocab)``) as the served ``(lm_head, mtp_heads)``: head
    0 and the further heads."""
    vocab = head.shape[1] // max(spec.pred_heads, 1)
    return head[:, :vocab], head[:, vocab:]


def split_qkvz(w, spec: ModelSpec):
    """A published ``in_proj_qkvz`` ``(d_model, 2 * Hk * d_k + 2 * Hv *
    d_v)`` or ``in_proj_ba`` ``(d_model, 2 * Hv)`` of a Gated DeltaNet layer
    (told apart by their width), whose columns go key head by key head
    (``[q | k | v | z]`` or ``[b | a]`` of one key head, then the next), as
    the served ``in_qkvz`` / ``in_ba``: every head's ``q``, then every
    head's ``k``, ``v``, ``z`` (or ``b``, then ``a``)."""
    hk, rep = spec.gdn_k_heads, spec.gdn_v_heads // spec.gdn_k_heads
    parts = ((spec.gdn_k_dim, spec.gdn_k_dim, rep * spec.gdn_v_dim,
              rep * spec.gdn_v_dim)
             if w.shape[1] != 2 * spec.gdn_v_heads else (rep, rep))
    w = w.reshape(w.shape[0], hk, sum(parts))
    cuts = np.cumsum((0,) + parts)
    return np.concatenate(
        [np.asarray(w[:, :, a:b]).reshape(w.shape[0], -1)
         for a, b in zip(cuts[:-1], cuts[1:])], axis=1)


def split_kv_b(kv_b, spec: ModelSpec):
    """A published ``kv_b_proj`` ``(kv_lora_rank, n_heads * (qk_nope +
    v_head_dim))``, a head's columns ``[k_nope | v]``, as ``(w_uk (H, nope,
    C), w_uv (H, C, v))``."""
    w = kv_b.reshape(spec.kv_lora_rank, spec.n_heads,
                     spec.qk_nope_head_dim + spec.v_head_dim)
    return (w[:, :, :spec.qk_nope_head_dim].transpose(1, 2, 0),
            w[:, :, spec.qk_nope_head_dim:].transpose(1, 0, 2))


def init_params(spec: ModelSpec, vocab: int, d_ff: int, seed: int = 0,
                scale: float = 0.02) -> Dict[str, Any]:
    """Seeded random float32 parameters in the layouts above: an MLA (+
    expert) decoder, a GQA decoder with an indexer or a Gated DeltaNet /
    attention hybrid, with an untied output head, or a Mamba/attention
    hybrid or a CCA decoder with a tied one (no ``lm_head``).
    Weights normal ``scale``, norm scales 1 (LayerNorm biases 0), the
    ``"sigmoid_bias"`` router's selection bias drawn like a weight (not
    zero: choosing with it and weighting without it must differ), the
    ``"softmax_bias"`` router's a unit normal over the router's columns
    (the scale of its scores).

    A Mamba layer's SSM leaves follow the published initialisation, not
    normal ``scale`` (under which every channel forgets within three tokens
    and a state that is dropped or rounded could not be seen in the
    logits): ``a_log = log(1..d_state)`` on every channel, ``d = 1``,
    ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3, 1e-1],
    the convolution uniform within ``d_conv ** -0.5``.  A Gated DeltaNet
    layer's for the same reason: ``a_log = log(U(0, 16))``, ``dt_bias`` and
    the convolution as Mamba's.  A Mamba-2 layer's by ITS published
    initialiser: ``a_log = log(U(1, 16))`` a head, ``d = 1``, ``dt_bias =
    softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3, 1e-1] floored at
    1e-4, the convolution's weight and bias uniform within ``d_conv **
    -0.5``; ``relu2`` experts are drawn at the published width and padded
    (:func:`nemotron_h_layout`).  The EVA scorers ``eva_mu`` / ``eva_phi``
    are a unit normal cut at two deviations (the published
    initialisation).  The hyper-connections' leaves are
    :func:`init_hyper_connection`'s; a CCA layer's convolutions, its key
    temperature, the residual scaling and the MLP router are
    :func:`zaya_leaf`'s (normal ``scale`` would switch each of them off)."""
    import jax
    import jax.numpy as jnp

    if (spec.attention != "mla" and not spec.state_layers
            and not spec.index_topk and not spec.attn_gate
            and not spec.eva_window and not spec.qk_norm):
        raise ValueError("init_params draws MLA decoders, hybrids with a "
                         "lane state, decoders with an indexer, an output "
                         "gate or EVA windows; dense ones come from "
                         "tpulab.models.transformer")
    # (twice the keys where zaya_leaf draws a layer's further leaves: the
    # other kinds keep the draws a seed always gave them)
    zaya = bool(spec.cca_taps or spec.res_scale or spec.router == "mlp")
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 (32 if zaya else 16) * spec.n_layers + 4))

    def w(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def norm(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def drawn(path, *shape):       # a leaf zaya_leaf draws, by its name
        return zaya_leaf(path, shape, next(keys))

    d, h = spec.d_model, spec.n_heads
    params: Dict[str, Any] = {"embed": w(vocab, d), "final_norm": norm(d)}
    if not spec.mamba_layers and not spec.cca_taps:
        params["lm_head"] = w(d, vocab)
    if spec.pred_heads > 1:
        params["mtp_heads"] = w(d, (spec.pred_heads - 1) * vocab)
    for i, kind in enumerate(spec.layer_kinds):
        p = {"ln1": norm(d), "ln2": norm(d)}
        if kind == "none":              # ONE norm a layer: the mixer's
            del p["ln2"]
        if spec.mixers[i] == "none":    # ... or the FFN's, and no mixer
            del p["ln1"]
        elif spec.mixers[i] == "mamba2":
            nh, din, cd = (spec.m2_heads, spec.m2_heads * spec.m2_head_dim,
                           spec.m2_conv_dim)
            bound = spec.d_conv ** -0.5
            dt = jnp.maximum(jnp.exp(uniform(np.log(1e-3), np.log(1e-1),
                                             nh)), 1e-4)
            p["mamba2"] = {
                "in_proj": w(d, din + cd + nh),
                "conv_w": uniform(-bound, bound, spec.d_conv, cd),
                "conv_b": uniform(-bound, bound, cd),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(uniform(1.0, 16.0, nh)),
                "d": jnp.ones((nh,), jnp.float32),
                "norm": norm(din),
                "out_proj": w(din, d)}
        elif spec.mixers[i] == "mamba":
            di, n, r = spec.d_inner, spec.d_state, spec.dt_rank
            bound = spec.d_conv ** -0.5
            dt = jnp.exp(uniform(np.log(1e-3), np.log(1e-1), di))
            p["mamba"] = {
                "in_proj": w(d, 2 * di),
                "conv_w": uniform(-bound, bound, spec.d_conv, di),
                "conv_b": uniform(-bound, bound, di),
                "x_proj": w(di, r + 2 * n),
                "dt_norm": norm(r), "b_norm": norm(n), "c_norm": norm(n),
                "dt_proj": w(r, di),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                    (n, di)),
                "d": jnp.ones((di,), jnp.float32),
                "out_proj": w(di, d)}
        elif spec.mixers[i] == "gdn":
            nk, nv = (spec.gdn_k_heads * spec.gdn_k_dim,
                      spec.gdn_v_heads * spec.gdn_v_dim)
            bound = spec.d_conv ** -0.5
            dt = jnp.exp(uniform(np.log(1e-3), np.log(1e-1),
                                 spec.gdn_v_heads))
            p["gdn"] = {
                "in_qkvz": w(d, 2 * nk + 2 * nv),
                "in_ba": w(d, 2 * spec.gdn_v_heads),
                "conv_w": uniform(-bound, bound, spec.d_conv, 2 * nk + nv),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(uniform(1e-6, 16.0, spec.gdn_v_heads)),
                "norm": norm(spec.gdn_v_dim),
                "out_proj": w(nv, d)}
        elif spec.mixers[i] == "cca":
            hd, c = spec.head_dim, (h + spec.n_kv_heads) * spec.head_dim
            p["cca"] = {
                "in_proj": w(d, c + spec.n_kv_heads * hd),
                "conv0_w": drawn("['conv0_w']", spec.cca_taps[0], c),
                "conv0_b": w(c),
                "conv1_w": drawn("['conv1_w']", spec.cca_taps[1],
                                 h + spec.n_kv_heads, hd, hd),
                "conv1_b": w(c),
                "tau": drawn("['tau']", spec.n_kv_heads)}
            p["wo"] = w(h * hd, d)
        elif spec.attention == "mla":
            p.update(
                wq_a=w(d, spec.q_lora_rank), q_norm=norm(spec.q_lora_rank),
                wq_b=w(spec.q_lora_rank, h * spec.qk_head_dim),
                wkv_a=w(d, spec.latent_width),
                kv_norm=norm(spec.kv_lora_rank),
                w_uk=w(h, spec.qk_nope_head_dim, spec.kv_lora_rank),
                w_uv=w(h, spec.kv_lora_rank, spec.v_head_dim),
                wo=w(h * spec.v_head_dim, d))
        else:
            hq = 2 * h if spec.attn_gate else h   # a head's [query | gate]
            p.update(wqkv=w(d, (hq + 2 * spec.n_kv_heads) * spec.head_dim),
                     wo=w(h * spec.head_dim, d))
            if spec.qk_norm:
                p.update(q_norm=norm(spec.head_dim),
                         k_norm=norm(spec.head_dim))
            if spec.eva_window:
                # the published scorers: a unit normal cut at two deviations
                p.update({name: jax.random.truncated_normal(
                    next(keys), -2.0, 2.0, (h, spec.head_dim), jnp.float32)
                    for name in ("eva_mu", "eva_phi")})
            if spec.index_topk:
                p["indexer"] = {
                    "wq": w(d, spec.index_heads * spec.index_dim),
                    "wk": w(d, spec.index_dim),
                    "k_norm": dict(norm(spec.index_dim), bias=jnp.zeros(
                        (spec.index_dim,), jnp.float32)),
                    "ww": w(d, spec.index_heads)}
        if kind not in ("moe", "none"):
            p.update(w1=w(d, d_ff), w3=w(d, d_ff), w2=w(d_ff, d))
        if kind == "moe" and spec.expert_act == "relu2":
            # two matrices an expert, the served width padded with zeros
            f, fs = spec.moe_ff, spec.n_shared * spec.moe_ff
            held = spec.experts_held or spec.ffn_experts
            w1, w2 = nemotron_h_layout(w(held, d, f), w(held, f, d), spec)
            p["moe"] = {"router": w(d, spec.n_experts),
                        "bias": w(spec.n_experts), "w1": w1, "w2": w2}
            if fs:
                p["shared"] = {"w1": w(d, fs), "w2": w(fs, d)}
        elif kind not in ("dense", "none"):
            f, fs = spec.moe_ff, spec.n_shared * spec.moe_ff
            held = spec.experts_held or spec.ffn_experts
            if spec.router == "mlp":
                r, at = spec.router_width, "['router']['%s']"
                router = {
                    "down": drawn(at % "down", d, r), "down_b": w(r),
                    "norm": norm(r), "w1": drawn(at % "w1", r, r),
                    "b1": w(r), "w2": drawn(at % "w2", r, r), "b2": w(r),
                    "w3": drawn(at % "w3", r, spec.n_experts)}
                if i:           # depth averaging: none on the first
                    router["gamma"] = drawn(at % "gamma", r)
            else:
                router = w(d, spec.n_experts)
            p["moe"] = {"router": router,
                        "bias": w(spec.n_experts),
                        "w13": w(held, d, 2 * f),
                        "w2": w(held, f, d)}
            if spec.router == "softmax":
                del p["moe"]["bias"]
            elif spec.router == "softmax_bias":
                # at the scale of the scores (1 / columns on average), not
                # of a weight: at ``scale`` it would outweigh every score
                # and choose the same columns for every row
                p["moe"]["bias"] = p["moe"]["bias"] / (scale
                                                       * spec.n_experts)
            if fs:
                p["shared"] = {"w1": w(d, fs), "w3": w(d, fs),
                               "w2": w(fs, d)}
                if spec.shared_gate:
                    p["shared"]["gate"] = w(d, 1)
        if spec.hc_mult:
            p.update(hc_attn=init_hyper_connection(next(keys), spec),
                     hc_ffn=init_hyper_connection(next(keys), spec))
        if spec.res_scale:
            for name in ("res_attn", "res_ffn"):
                p[name] = {"s_r": drawn("['s_r']", d), "b_r": w(d),
                           "s_o": drawn("['s_o']", d), "b_o": w(d)}
        params[f"layer{i}"] = p
    return params


def zaya_leaf(path: str, shape, key):
    """One leaf of a ``zaya`` tree that is NOT drawn normal 0.02, float32,
    by the end of its tree path (``jax.tree_util.keystr``); None for every
    other leaf.  Each constant is where normal 0.02 would switch the leaf's
    mechanism off and a program without it would pass for one with it:

    ``conv0_w`` normal 0.7 and ``conv1_w`` normal ``(taps * head_dim)^-0.5``:
    the convolved part of ``q`` and ``k`` about as large as the q-k mean it
    is added to (at 0.02 it is 3 % of it, and a lost tail would not show);
    ``tau`` uniform 0.8-1.2 (not 0.02: a key of that length is no key);
    the router's ``down`` normal ``d_model^-0.5``, ``w1`` and ``w2`` normal
    ``1.5 W^-0.5``, ``w3`` normal ``2 W^-0.5``, ``gamma`` uniform 0.5-1: the
    softmax has another largest column from token to token and the state
    handed on changes the choice (at 0.02 ``p`` is flat and the selection
    bias alone picks one column for every token).  ``w2`` and ``w3`` have
    each column's mean over its inputs taken out: a GELU's outputs share a
    positive mean, which a column that does not sum to zero turns into an
    offset of its own, the same for every token, and at these widths the
    offsets outweigh what a token adds (without it a column took 69 % of
    4,096 rows and others none; with it every column of every layer takes
    2-12 %);
    ``s_r`` and ``s_o`` uniform 0.8-1.2 (a residual scaled by 0.02 ends the
    signal in two layers)."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("['", 1)[-1].rstrip("']")
    if "['router']" in path:
        std = {"down": shape[0] ** -0.5, "w1": 1.5 * shape[0] ** -0.5,
               "w2": 1.5 * shape[0] ** -0.5,
               "w3": 2.0 * shape[0] ** -0.5}.get(name)
        if name == "gamma":
            return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.0)
        if name in ("w2", "w3"):
            w = std * jax.random.normal(key, shape, jnp.float32)
            return w - w.mean(axis=0, keepdims=True)
    elif name in ("tau", "s_r", "s_o"):
        return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
    else:
        std = {"conv0_w": 0.7,
               "conv1_w": (shape[0] * shape[-1]) ** -0.5}.get(name)
    if std is None:
        return None
    return std * jax.random.normal(key, shape, jnp.float32)


def init_hyper_connection(key, spec: ModelSpec) -> Dict[str, Any]:
    """Seeded float32 leaves of ONE sublayer's hyper-connection (``norm``,
    ``phi``, ``alpha``, ``bias``; the module docstring has the layout).
    Not normal 0.02: with a small ``phi`` the three maps are constants and
    ``H_res`` the uniform matrix, which a program that ignored the streams
    would compute as well.  ``phi`` is normal ``(n d_model)^-0.5`` (the
    normed streams have unit mean square, so a projection has deviation
    about 1), ``alpha`` 1, the biases of ``h_pre`` and ``h_post`` a unit
    normal, those of ``H_res`` normal 0.5 and 1 higher on the diagonal:
    every token has its own maps, and ``H_res`` is neither uniform nor a
    permutation (at twice these two numbers a fifth of the sublayers drew
    an entry past 0.95, and twenty sweeps left their rows 2e-3 off 1)."""
    import jax
    import jax.numpy as jnp

    n, nc = spec.hc_mult, spec.hc_mult * spec.d_model
    k_phi, k_bias = jax.random.split(key)
    bias = jax.random.normal(k_bias, (2 * n + n * n,), jnp.float32)
    bias = bias.at[2 * n:].multiply(0.5)
    return {"norm": {"scale": jnp.ones((nc,), jnp.float32)},
            "phi": jax.random.normal(k_phi, (nc, 2 * n + n * n),
                                     jnp.float32) * nc ** -0.5,
            "alpha": jnp.ones((3,), jnp.float32),
            "bias": bias.at[2 * n:].add(jnp.eye(n).reshape(-1))}
