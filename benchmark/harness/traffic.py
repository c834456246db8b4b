"""The one generator of inputs: weights and the batch, from the seed.

A traffic mix is a data file under `benchmark/traffic/`; its `kind` names the
maker that reads its parameters: one of `KINDS` below or, for a batch that
none of them makes, one of the `KINDS` that the configuration's reference
module brings, `maker(key, traffic, cfg) -> {name: array}`. Everything is
made on the device in one jitted call, in the type it is used in, and the
same seed gives the same arrays.
"""
import jax
import jax.numpy as jnp

from .spec import lookup


def seed_key(seed):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _images(key, traffic, cfg):
    """Unit-normal images whose rows all differ, uniform labels."""
    kx, ky = jax.random.split(key)
    size = traffic["image_size"]
    x = jax.random.normal(kx, (traffic["batch"], 3, size, size), jnp.float32)
    y = jax.random.randint(ky, (traffic["batch"],), 0, cfg["classes"])
    return {"data": x.astype(jnp.dtype(cfg["dtype"])),
            "label": y.astype(jnp.float32)}


def _mlm_tokens(key, traffic, cfg):
    """Uniform token ids and targets; each position is a prediction site
    with the mix's share."""
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (traffic["batch"], traffic["seq"])
    return {"tokens": jax.random.randint(k1, shape, 0, cfg["vocab_size"]),
            "targets": jax.random.randint(k2, shape, 0, cfg["vocab_size"]),
            "mask": (jax.random.uniform(k3, shape) < traffic["mask_share"]
                     ).astype(jnp.int32)}


KINDS = {"images": _images, "mlm_tokens": _mlm_tokens}


def samples_per_step(traffic):
    """What a step counts as done: images, or tokens (masked or not)."""
    return traffic["batch"] * traffic.get("seq", 1)


def make(seed, reference, cfg, traffic):
    """(params, batch): the reference's leaves and the mix's batch, in one
    jitted call from the seed."""
    maker = lookup("KINDS", traffic["kind"], KINDS, reference)

    @jax.jit
    def both(key):
        kp, kb = jax.random.split(key)
        return reference.init_params(kp, cfg), maker(kb, traffic, cfg)
    return both(seed_key(seed))
