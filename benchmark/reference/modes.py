"""The precisions a plain reference can be computed in.

  "f32"   the reference proper: float32, products at `highest`
  "bf16"  every operand of a product and every activation kept rounded to
          bfloat16
  "bf16_stack"
          "bf16", and a convolution's output stored in bfloat16 as well, as
          a bfloat16 convolution stack keeps it in front of its float32
          BatchNorm: every read of it sees it rounded and gives its
          cotangent back rounded by itself. It is the float32 reference's
          own reading at the precision a bfloat16 configuration states, and
          says which leaves' gradients that precision resolves
          (`harness/correct.py`); it is no control
  "fp8"   an fp8 training path: every operand of a forward product rounded
          to float8_e4m3 and every cotangent that a backward product takes to
          float8_e5m2, each with one scale per tensor; activations kept in
          bfloat16, as such paths keep them

The controls of the benchmark's comparison are the reference put in the
program's place, one precision below what its configuration states:

  resnet50_v1 (bfloat16 stack, float32 BatchNorm)    "fp8"
  bert_base (float32 weights, moments and activations with bfloat16
      products)    "bf16" with weights and moments kept in bfloat16 too
      (`ReferenceRunner(mode="bf16", stored="bfloat16")`): bfloat16 for
      float32. "bf16" alone is no step down: its products are the
      configuration's own and only the activations' rounding is added. "fp8",
      two steps down, was read as well and the limits hold against both.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _scaled(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    return _scaled(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_scaled(g, jnp.float8_e5m2, 57344.0),))


def operand(x, mode):
    """What a product sees of one of its operands."""
    if mode in ("bf16", "bf16_stack"):
        return x.astype(jnp.bfloat16).astype(F32)
    if mode == "fp8":
        return _fp8(x)
    return x


def activation(x, mode):
    """What is kept of a layer's output."""
    if mode in ("bf16", "bf16_stack", "fp8"):
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def stored(x, mode):
    """One read of a product's output by the layer behind it."""
    if mode == "bf16_stack":
        return x.astype(jnp.bfloat16).astype(F32)
    return x
