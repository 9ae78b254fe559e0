"""DeepSeek-V2-Lite — multi-head latent attention + DeepSeekMoE
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json].
27L d2048 16H MLA (no q-LoRA, kv_lora_rank 512, qk_nope 128, qk_rope 64,
v 128), YaRN rope (factor 40 over 4096); layer 0 dense (d_ff 10944), then
64 routed experts (d_ff 1408, softmax top-6, gates not renormalised,
routed_scaling_factor 1) + 2 shared experts; rms_norm_eps 1e-6; vocab
102400.  Every mechanism is set here explicitly, so a default elsewhere
cannot change this model."""
import jax.numpy as jnp

from repro.models.layers import ModelConfig

FULL = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=102400,
    moe_experts=64, moe_top_k=6, moe_shared_experts=2,
    moe_first_dense=True, dense_ff=10944,
    moe_norm_topk=False, moe_capacity_factor=0.0,
    norm_eps=1e-6, rope_theta=1e4,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
)

#: every mechanism of FULL at CPU-test size: dense layer 0 then one MoE
#: layer, 8 experts top-2 + 2 shared, MLA ranks 16 / rope 8 / nope 16 / v 16
SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke", family="moe",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=32, vocab=128,
    moe_experts=8, moe_top_k=2, moe_shared_experts=2,
    moe_first_dense=True, dense_ff=128,
    moe_norm_topk=False, moe_capacity_factor=0.0,
    norm_eps=1e-6, rope_theta=1e4,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
    dtype=jnp.float32, remat=False,
)


def to_hf(cfg: ModelConfig) -> dict:
    """``cfg`` under the keys of the model's published ``config.json`` (what
    ``models/reference_deepseek_v2.py`` and the benchmark's configuration
    file read)."""
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.dense_ff,
        "moe_intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
        "n_routed_experts": cfg.moe_experts,
        "num_experts_per_tok": cfg.moe_top_k,
        "n_shared_experts": cfg.moe_shared_experts,
        "first_k_dense_replace": int(cfg.moe_first_dense),
        "moe_layer_freq": cfg.moe_every,
        "norm_topk_prob": cfg.moe_norm_topk, "routed_scaling_factor": 1,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.yarn_factor,
            "original_max_position_embeddings": cfg.yarn_original_max_pos,
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "mscale": cfg.yarn_mscale,
            "mscale_all_dim": cfg.yarn_mscale_all_dim},
    }


def from_hf(conf: dict, dtype=jnp.float32) -> ModelConfig:
    """The :class:`ModelConfig` of a DeepSeek-V2 ``config.json`` dict (the
    inverse of :func:`to_hf`); refuses what this model code does not
    compute (q-LoRA, grouped routing, a routed scale other than 1)."""
    if conf.get("q_lora_rank") or conf.get("n_group", 1) != 1 \
            or conf.get("routed_scaling_factor", 1) != 1 \
            or conf.get("moe_layer_freq", 1) != 1 \
            or conf.get("first_k_dense_replace", 1) != 1:
        raise ValueError("only DeepSeek-V2-Lite's routing and attention "
                         "layout is supported")
    rs = conf["rope_scaling"]
    return ModelConfig(
        name=f"deepseek-v2-{conf['num_hidden_layers']}l", family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["moe_intermediate_size"], vocab=conf["vocab_size"],
        moe_experts=conf["n_routed_experts"],
        moe_top_k=conf["num_experts_per_tok"],
        moe_shared_experts=conf["n_shared_experts"],
        moe_first_dense=True, dense_ff=conf["intermediate_size"],
        moe_norm_topk=bool(conf["norm_topk_prob"]), moe_capacity_factor=0.0,
        norm_eps=conf["rms_norm_eps"], rope_theta=conf["rope_theta"],
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        yarn_factor=rs["factor"],
        yarn_original_max_pos=rs["original_max_position_embeddings"],
        yarn_beta_fast=rs["beta_fast"], yarn_beta_slow=rs["beta_slow"],
        yarn_mscale=rs["mscale"], yarn_mscale_all_dim=rs["mscale_all_dim"],
        dtype=dtype, remat=False)


def reference_params(params, cfg: ModelConfig) -> dict:
    """The program's parameter tree (``transformer.init``'s layout) in the
    layout ``models/reference_deepseek_v2.py`` reads: one dict a layer
    (views where ``params`` holds numpy arrays)."""
    from repro.models.pim_bridge import per_layer_params
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
            "layers": [{**lp["mixer"], "norm1": lp["norm1"],
                        "norm2": lp["norm2"], "ffn": lp["ffn"]}
                       for lp in per_layer_params(params, cfg)]}
