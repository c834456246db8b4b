"""BERT's masked-LM loss over the functional encoder, as a user hands it to
`parallel.ShardedTrainStep` (the recipe of `chip_smoke.py`)."""
import jax.numpy as jnp

from mxnet_tpu.models.bert import BertConfig, bert_mlm_loss


def loss_fn(cfg):
    """`loss_fn(params, batch)` of the configuration `cfg`."""
    bert = BertConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["dim"],
        n_layers=cfg["n_layers"], n_heads=cfg["n_heads"],
        hidden_dim=cfg["hidden_dim"], max_seq_len=cfg["max_seq_len"],
        n_types=cfg["n_types"], norm_eps=cfg["norm_eps"],
        dtype=jnp.dtype(cfg["dtype"]))
    return lambda params, batch: bert_mlm_loss(params, batch, bert)
