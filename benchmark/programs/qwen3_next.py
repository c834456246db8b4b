"""Qwen3-Next's next-token loss over the functional decoder, as a user hands
it to `parallel.ShardedTrainStep`: one chip's share of the experts and of
the vocabulary, named by the configuration."""
import jax.numpy as jnp

from mxnet_tpu.models.qwen3_next import Qwen3NextConfig, qwen3_next_loss


def loss_fn(cfg):
    """`loss_fn(params, batch)` of the configuration `cfg`."""
    model = Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["n_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"],
        linear_key_heads=cfg["linear_num_key_heads"],
        linear_value_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"], chunk=cfg["chunk"],
        n_routed_experts=cfg["n_experts_published"],
        n_experts=cfg["n_experts"], first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_expert_dim=cfg["shared_expert_intermediate_size"],
        moe_rows_bound=cfg["moe_rows_bound"], norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["dtype"]))
    return lambda params, batch: qwen3_next_loss(params, batch, model)
