"""From a configuration file to a compiled server: the program's
``DecoderConfig``, the mesh the file's ``machine`` describes, weights
drawn on the device(s) in ONE jitted call under the family's own
shardings, and ``LLM(...).compile(ServingConfig(...))``."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def family_of(config):
    return importlib.import_module(f"flexflow_tpu.models.{config['family']}")


def decoder_config(config, **overrides):
    """The program's config object from the file's published keys (the
    file holds Hugging Face's ``config.json`` names; the family's own
    ``from_hf`` reads them)."""
    dtype = jnp.dtype(config["dtype"])
    return family_of(config).from_hf(config, dtype=dtype, **overrides)


def make_mesh(config):
    from flexflow_tpu.core.mesh import MachineSpec

    machine = MachineSpec(**config["machine"])
    return machine.make_mesh(jax.devices()[: machine.num_devices])


def _leaf_std(path, num_layers):
    """0.02, and 0.02 / sqrt(2 N) for the two projections that write
    into the residual stream (GPT-2's rule, which the families' own
    ``init_params`` follows too): logits stay of order one at any depth."""
    name = path[-1].key
    if name in ("wo", "w_down"):
        return 0.02 / (2 * num_layers) ** 0.5
    return 0.02


def make_params(family, cfg, mesh, key):
    """Seeded random weights in the served dtype, made where they will
    live: one jitted program whose ``out_shardings`` are the family's
    ``param_pspecs`` on ``mesh``, so no device ever holds more than its
    share and ``LLM._place_params`` finds every array in place. The
    tree's STRUCTURE is the program's (``eval_shape`` of its
    ``init_params``); the values are the benchmark's: norm scales one,
    biases zero, the rest normal."""
    shapes = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    shardings = jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        family.param_pspecs(cfg),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = path[-1].key
            if "norm_scale" in name:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif "bias" in name or name[0] == "b":  # bq, b_up, ...
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                std = _leaf_std(path, cfg.num_hidden_layers)
                x = jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, jnp.float32
                )
                out.append((x * std).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw, out_shardings=shardings)(key)


def serving_config(config, **overrides):
    from flexflow_tpu.serve import ServingConfig

    kw = dict(config["serving"])
    kw.update(overrides)
    # "retrace": a step key that compiles twice raises (the window may
    # hold no compile at all; run.py counts those separately)
    return ServingConfig(sanitizers=("retrace",), **kw)


def build_server(config, seed, *, params=None, **serving_overrides):
    """(llm, params): the compiled server and the weights it was given
    (drawn from ``seed`` unless handed in; the reference reads the same
    arrays). ``serving_overrides``: the arms of ``tools/control.py``."""
    from flexflow_tpu.serve.llm import LLM

    family = family_of(config)
    cfg = decoder_config(config)
    mesh = make_mesh(config)
    if params is None:
        params = make_params(family, cfg, mesh, seed_key(seed))
    llm = LLM(family, cfg, params=params, mesh=mesh)
    llm.compile(serving_config(config, **serving_overrides), seed=0)
    return llm, params


def param_bytes(params):
    return int(sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(params)))
