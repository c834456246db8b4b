"""Operator library: the registry plus all op-definition modules.

Importing this package registers every op (reference: static registration of
NNVM_REGISTER_OP at libmxnet.so load time).
"""
from . import registry
from .registry import register, alias, get, list_ops

from . import tensor      # noqa: F401  elementwise/broadcast/reduce/shape
from . import nn          # noqa: F401  FC/conv/pool/norm/softmax/dropout
from . import random_ops  # noqa: F401  sampling ops
from . import optimizer_ops  # noqa: F401  sgd/adam/... update kernels
from . import rnn_ops      # noqa: F401  fused RNN/LSTM/GRU via lax.scan
from . import quantization_ops  # noqa: F401  int8 quantize/dequant/QFC/QConv
from . import extended     # noqa: F401  linalg_* / multi_* / LRN / SVM / ST
from . import contrib_vision  # noqa: F401  box_nms/ROIAlign/resize/adaptive
from . import image_ops    # noqa: F401  _image_* family (nd.image/sym.image)
from . import grad_rules   # noqa: F401  FGradient-style vjp rules (hot ops)
from . import fused_optimizer  # noqa: F401  Pallas fused optimizer kernels
from . import sparse_ops   # noqa: F401  Pallas sparse segment-sum scatter-add
from . import shape_hints  # noqa: F401  FInferShape-style param-shape hints
