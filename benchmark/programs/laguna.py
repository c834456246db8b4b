"""Laguna's next-token loss over the functional decoder, as a user hands it
to `parallel.ShardedTrainStep`: one chip's share of the heads, of the
experts and of the vocabulary, named by the configuration."""
import jax.numpy as jnp

from mxnet_tpu.models.laguna import LagunaConfig, laguna_loss


def loss_fn(cfg):
    """`loss_fn(params, batch)` of the configuration `cfg`."""
    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    n = cfg["n_layers"]
    model = LagunaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=n,
        layer_types=tuple(cfg["layer_types"][:n]),
        heads_per_layer=tuple(cfg["num_attention_heads_per_layer"][:n]),
        n_kv_heads_published=cfg["n_kv_heads_published"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"],
        rope_sliding_theta=sliding["rope_theta"],
        rope_full_theta=full["rope_theta"],
        rope_full_partial=full["partial_rotary_factor"],
        rope_full_factor=full["factor"],
        rope_full_original_positions=full["original_max_position_embeddings"],
        rope_full_beta_fast=full["beta_fast"],
        rope_full_beta_slow=full["beta_slow"],
        rope_full_attention_factor=full["attention_factor"],
        dense_layers=tuple(i for i, kind in enumerate(
            cfg["mlp_layer_types"][:n]) if kind == "dense"),
        dense_dim=cfg["intermediate_size"],
        n_routed_experts=cfg["n_experts_published"],
        n_experts=cfg["n_experts"], first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        routed_scale=cfg["moe_routed_scaling_factor"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_expert_dim=cfg["shared_expert_intermediate_size"],
        moe_rows_bound=cfg["moe_rows_bound"], norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["dtype"]))
    return lambda params, batch: laguna_loss(params, batch, model)
