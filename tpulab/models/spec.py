"""Model spec: what the paged engine has to know about a decoder's layers.

The step functions of :mod:`tpulab.engine.paged_steps` run ONE layer block
(``paged_steps._layer_block``) for every model they serve; a :class:`ModelSpec`
tells it the attention kind and its widths, which layers carry a dense FFN
and which a routed expert FFN, the RMSNorm epsilon, and with the attention
kind the *cache-entry kind* the page store holds:

``"kv"``      K and V rows of ``n_kv_heads * head_dim`` values (MHA/GQA);
``"latent"``  one row ``[c_kv ; k_rope]`` of ``kv_lora_rank +
              qk_rope_head_dim`` values a token a layer (multi-head latent
              attention, served in the absorbed form).

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
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple


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
    layer_kinds: Tuple[str, ...] = ()       # "dense" | "moe", one a layer
    n_experts: int = 0
    top_k: int = 0
    moe_ff: int = 0
    n_shared: int = 0
    routed_scale: float = 1.0
    norm_topk: bool = True
    rms_eps: float = 1e-6
    rope_theta: Optional[float] = None

    def __post_init__(self):
        if self.attention not in ("gqa", "mla"):
            raise ValueError(f"unknown attention kind {self.attention!r}")
        kinds = self.layer_kinds or ("dense",) * self.n_layers
        if len(kinds) != self.n_layers or set(kinds) - {"dense", "moe"}:
            raise ValueError(f"layer_kinds {kinds} does not name a dense or "
                             f"moe FFN for each of {self.n_layers} layers")
        object.__setattr__(self, "layer_kinds", tuple(kinds))

    @property
    def cache_entry(self) -> str:
        """What a token leaves in the page store: ``"kv"`` or ``"latent"``."""
        return "latent" if self.attention == "mla" else "kv"

    @property
    def latent_width(self) -> int:
        """Values of a latent row: ``[c_kv ; k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == "moe")


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
    """Seeded random float32 parameters of an MLA (+ expert) decoder in the
    layout above: weights normal ``scale``, norm scales 1, the router's
    selection bias drawn like a weight (not zero: choosing with it and
    weighting without it must differ).  Untied output head."""
    import jax
    import jax.numpy as jnp

    if spec.attention != "mla":
        raise ValueError("init_params draws MLA decoders; dense ones come "
                         "from tpulab.models.transformer")
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 16 * spec.n_layers + 4))

    def w(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def norm(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    d, h = spec.d_model, spec.n_heads
    params: Dict[str, Any] = {"embed": w(vocab, d), "final_norm": norm(d),
                              "lm_head": w(d, vocab)}
    for i, kind in enumerate(spec.layer_kinds):
        p = {"ln1": norm(d), "ln2": norm(d),
             "wq_a": w(d, spec.q_lora_rank), "q_norm": norm(spec.q_lora_rank),
             "wq_b": w(spec.q_lora_rank, h * spec.qk_head_dim),
             "wkv_a": w(d, spec.latent_width),
             "kv_norm": norm(spec.kv_lora_rank),
             "w_uk": w(h, spec.qk_nope_head_dim, spec.kv_lora_rank),
             "w_uv": w(h, spec.kv_lora_rank, spec.v_head_dim),
             "wo": w(h * spec.v_head_dim, d)}
        if kind == "dense":
            p.update(w1=w(d, d_ff), w3=w(d, d_ff), w2=w(d_ff, d))
        else:
            f, fs = spec.moe_ff, spec.n_shared * spec.moe_ff
            p["moe"] = {"router": w(d, spec.n_experts),
                        "bias": w(spec.n_experts),
                        "w13": w(spec.n_experts, d, 2 * f),
                        "w2": w(spec.n_experts, f, d)}
            p["shared"] = {"w1": w(d, fs), "w3": w(d, fs), "w2": w(fs, d)}
        params[f"layer{i}"] = p
    return params
