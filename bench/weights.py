"""Random weights of a dense GQA decoder, made on the device from a seed.

The benchmark owns its weights: the program under test is handed them (and
packs them with its own PTQ where it serves NVFP4), and the plain reference
makes the same ones again from the same seed.  The tree has the layout of
``repro.models.decoder``'s parameters (layers stacked on a leading axis),
which the cell drivers check against the program's own specs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims_of(conf: dict) -> dict:
    """Model sizes from a configuration file's published keys."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {"d_model": d, "n_layers": conf["num_hidden_layers"],
            "n_heads": h, "n_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf.get("head_dim", d // h),
            "d_ff": conf["intermediate_size"],
            "vocab_size": conf["vocab_size"],
            "tie_embeddings": bool(conf["tie_word_embeddings"]),
            "rope_theta": float(conf["rope_theta"]),
            "rms_norm_eps": float(conf["rms_norm_eps"]),
            "nvfp4_activations": conf["activations"] == "nvfp4"}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def shapes(dims: dict) -> dict:
    """{path: (shape, init, std)} of every leaf, in a fixed order."""
    d, L, ff, v = (dims["d_model"], dims["n_layers"], dims["d_ff"],
                   dims["vocab_size"])
    hd, h, hkv = dims["head_dim"], dims["n_heads"], dims["n_kv_heads"]
    qkv = (h + 2 * hkv) * hd
    out = {
        "embed": ((v, d), "normal", 0.02),
        "layers/ln1/w": ((L, d), "norm", 0.1),
        "layers/wqkv": ((L, d, qkv), "normal", d ** -0.5),
        "layers/bqkv": ((L, qkv), "normal", 0.02),
        "layers/wo": ((L, h * hd, d), "normal", 0.5 * (h * hd) ** -0.5),
        "layers/ln2/w": ((L, d), "norm", 0.1),
        "layers/wg": ((L, d, ff), "normal", d ** -0.5),
        "layers/wu": ((L, d, ff), "normal", d ** -0.5),
        "layers/wd": ((L, ff, d), "normal", 0.5 * ff ** -0.5),
        "final_norm/w": ((d,), "norm", 0.1),
    }
    if not dims["tie_embeddings"]:
        out["lm_head"] = ((d, v), "normal", d ** -0.5)
    return out


def _leaf(key, shape, init, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * std
    if init == "norm":
        x = x + 1.0
    return x.astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(dims: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree (traceable: jit it to build on device)."""
    flat = {path: _leaf(jax.random.fold_in(key, i), shape, init, std, dtype)
            for i, (path, (shape, init, std))
            in enumerate(shapes(dims).items())}
    return _nest(flat)


@functools.lru_cache(maxsize=None)
def _jitted(items: tuple, dtype):
    return jax.jit(lambda key: make(dict(items), key, dtype))


def build(dims: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf made on the device in one jitted call from the seed."""
    return _jitted(tuple(sorted(dims.items())), dtype)(seed_key(seed))
