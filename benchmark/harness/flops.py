"""Required operations and bytes, counted from shapes.

What the algorithm needs, not what the compiler emitted: recomputation is not
counted, a training step is three forward passes' worth of products (forward,
gradient to the input, gradient to the weights). Only a `benchmark` PR may
change a formula here. A later PR brings its own in the reference module of
its configuration, in tables named as the two at the end of this file, and
names the function in the configuration's `flops` key or in a metric's
`work` parameter (`train_flops_per_sample`, `kernel_work`):

  TRAIN_FLOPS_PER_SAMPLE[name](cfg, traffic) -> operations one sample (an
      image, a token) requires of one training step
  KERNEL_WORK[name](cfg, traffic) -> (operations, bytes) one training step
      requires of the kernel, whole batch
"""
from .spec import lookup

F32 = 4      # bytes


def _itemsize(cfg):
    """Bytes of one element of the type the configuration computes in."""
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["dtype"]]


# ---------------------------------------------------------------- ResNet v1
def resnet_v1_layers(cfg, image_size):
    """[(name, k, cin, cout, h_in, h_out)] of every convolution of the
    model zoo's ResNet v1, in forward order; the dense layer comes last as a
    1x1 'convolution' on a 1x1 map."""
    out = []

    def conv(name, cout, cin, k, stride, pad, h):
        ho = (h + 2 * pad - k) // stride + 1
        out.append((name, k, cin, cout, h, ho))
        return ho

    chans = cfg["channels"]
    h = conv("stem.conv", chans[0], 3, 7, 2, 3, image_size)
    h = (h + 2 - 3) // 2 + 1                        # max-pool 3x3/2 pad 1
    for i, n in enumerate(cfg["layers"]):
        cin, cout = chans[i], chans[i + 1]
        for j in range(n):
            pre = "s%d.b%d." % (i + 1, j)
            stride = (1 if i == 0 else 2) if j == 0 else 1
            c = cin if j == 0 else cout
            if j == 0 and cout != cin:
                conv(pre + "ds", cout, c, 1, stride, 0, h)
            if cfg["block"] == "bottleneck":
                mid = cout // 4
                h2 = conv(pre + "c1", mid, c, 1, stride, 0, h)
                conv(pre + "c2", mid, mid, 3, 1, 1, h2)
                conv(pre + "c3", cout, mid, 1, 1, 0, h2)
            else:
                h2 = conv(pre + "c1", cout, c, 3, stride, 1, h)
                conv(pre + "c2", cout, cout, 3, 1, 1, h2)
            h = h2
    out.append(("fc", 1, chans[-1], cfg["classes"], 1, 1))
    return out


def resnet_v1_forward_flops(cfg, traffic):
    """2*k*k*cin*cout*Hout*Wout over every convolution and the dense layer:
    one image, forward."""
    return sum(2 * k * k * cin * cout * ho * ho
               for _, k, cin, cout, _, ho in
               resnet_v1_layers(cfg, traffic["image_size"]))


def resnet_v1_train_flops(cfg, traffic):
    """One image, one training step."""
    return 3 * resnet_v1_forward_flops(cfg, traffic)


def resnet_v1_conv_work(cfg, traffic):
    """(flops, bytes) one training step requires of the convolutions and the
    dense layer, whole batch: each of the three products of a layer reads two
    of {input, output, weight} and writes the third, in the configuration's
    type."""
    batch = traffic["batch"]
    flops = batch * resnet_v1_train_flops(cfg, traffic)
    nbytes = 0
    for _, k, cin, cout, hi, ho in resnet_v1_layers(cfg,
                                                    traffic["image_size"]):
        nbytes += 3 * _itemsize(cfg) * (batch * cin * hi * hi + batch * cout * ho * ho
                              + k * k * cin * cout)
    return flops, nbytes


# --------------------------------------------------------------------- BERT
def bert_forward_flops(cfg, traffic):
    """One token, forward: the matrix products of every layer (q, k, v, o:
    4*d*d; the two of the feed-forward: 2*d*h), the tied decoder over every
    position (d*V), and the attention core (scores and weighted sum:
    4*S*d a layer)."""
    d, h, L = cfg["dim"], cfg["hidden_dim"], cfg["n_layers"]
    return (2 * (L * (4 * d * d + 2 * d * h) + d * cfg["vocab_size"])
            + L * 4 * traffic["seq"] * d)


def bert_train_flops(cfg, traffic):
    """One token, one training step."""
    return 3 * bert_forward_flops(cfg, traffic)


def attention_core_work(cfg, traffic):
    """(flops, bytes) one training step requires of the attention core, whole
    batch. Bytes: the forward reads q, k, v and writes o; the backward reads
    q, k, v, o, do and writes dq, dk, dv: twelve passes over a (B, H, S, D)
    tensor of the configuration's type a layer; and the row statistics (one float32 a row and
    head, written once and read twice)."""
    d, L, H = cfg["dim"], cfg["n_layers"], cfg["n_heads"]
    tokens = traffic["batch"] * traffic["seq"]
    flops = tokens * 3 * L * 4 * traffic["seq"] * d
    nbytes = tokens * L * (12 * d * _itemsize(cfg) + 3 * H * F32)
    return flops, nbytes


TRAIN_FLOPS_PER_SAMPLE = {
    "resnet_v1": resnet_v1_train_flops,
    "bert": bert_train_flops,
}
KERNEL_WORK = {
    "resnet_v1_conv": resnet_v1_conv_work,
    "attention_core": attention_core_work,
}


def train_flops_per_sample(cfg, traffic, reference=None):
    """Required operations a sample, by the configuration's `flops` name."""
    return lookup("TRAIN_FLOPS_PER_SAMPLE", cfg["flops"],
                  TRAIN_FLOPS_PER_SAMPLE, reference)(cfg, traffic)


def kernel_work(name, cfg, traffic, reference=None):
    """(operations, bytes) a step requires of the kernel `name`."""
    return lookup("KERNEL_WORK", name, KERNEL_WORK, reference)(cfg, traffic)


def roofline_seconds(flops, nbytes, peak):
    """(seconds, bound): the least time the chip could take, and which of
    the two peaks sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return ((t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes"))
