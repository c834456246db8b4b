"""First-class model families (TPU-native, functional JAX).

The reference ships vision models in ``gluon/model_zoo/vision`` (mirrored
here under :mod:`mxnet_tpu.gluon.model_zoo`) and relies on external GluonNLP
for transformers. The TPU build promotes transformers to first-class
citizens because the north-star configs (BERT-base, Llama-3-8B sharded)
require them: these are pure-functional param-tree models designed to
compose with :mod:`mxnet_tpu.parallel` (sharding rules, flash/ring
attention, fused train step).
"""
from . import llama
from . import bert
from . import resnet
from . import dlrm
from . import qwen3_next
from . import laguna
from .losses import linear_cross_entropy
from .llama import (LlamaConfig, llama_init, llama_forward, llama_loss,
                    llama_prefill_paged, llama_decode_paged,
                    llama_chunk_paged, llama_draft_loop, init_kv_pools)
from .bert import BertConfig, bert_init, bert_forward, bert_mlm_loss
from .resnet import ResNetConfig, resnet_init, resnet_forward, resnet_loss
from .dlrm import DLRMConfig, dlrm_init, dlrm_forward, dlrm_loss
from .qwen3_next import (Qwen3NextConfig, qwen3_next_init,
                         qwen3_next_forward, qwen3_next_loss)
from .laguna import LagunaConfig, laguna_init, laguna_forward, laguna_loss

__all__ = [
    "llama", "bert", "resnet", "dlrm", "qwen3_next", "laguna",
    "LlamaConfig", "llama_init", "llama_forward", "llama_loss",
    "llama_prefill_paged", "llama_decode_paged", "llama_chunk_paged",
    "llama_draft_loop", "init_kv_pools",
    "BertConfig", "bert_init", "bert_forward", "bert_mlm_loss",
    "ResNetConfig", "resnet_init", "resnet_forward", "resnet_loss",
    "DLRMConfig", "dlrm_init", "dlrm_forward", "dlrm_loss",
    "Qwen3NextConfig", "qwen3_next_init", "qwen3_next_forward",
    "qwen3_next_loss",
    "LagunaConfig", "laguna_init", "laguna_forward", "laguna_loss",
    "linear_cross_entropy",
]
