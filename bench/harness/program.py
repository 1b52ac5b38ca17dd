"""The adapter between the benchmark's files and the program under test.

Only the loops use it. It builds the program's architecture config from a
configuration file's sizes, and converts parameter trees between the
program's pytree and the benchmark's flat names ("embed.w",
"layers.wq.w_scale", ...).
"""
from __future__ import annotations

import jax

ARCH_KEYS = {  # configuration file key -> program ArchConfig field
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
}


def arch_config(c: dict):
    """The program's ArchConfig for configuration file `c`: the registry's
    entry for `c["arch"]` with the file's sizes."""
    from repro.configs.registry import get_config
    cfg = get_config(c["arch"])
    return cfg.replace(**{f: type(getattr(cfg, f))(c[k])
                          for k, f in ARCH_KEYS.items() if k in c})


def _name(path) -> str:
    keys = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            keys.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            keys.append(str(k.idx))
        else:
            keys.append(str(getattr(k, "name", k)))
    if keys[0] == "groups":   # scan groups: one pattern position, stacked
        if keys[1] != "0":
            raise ValueError(f"only one-block patterns are mapped: {keys}")
        return "layers." + ".".join(keys[2:])
    return ".".join(keys)


def flat_names(tree) -> list:
    """Flat benchmark names of the tree's leaves, in leaf order."""
    return [_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def to_flat(tree) -> dict:
    return dict(zip(flat_names(tree), jax.tree.leaves(tree)))


def from_flat(flat: dict, like):
    """Fill the structure of `like` from `flat` by name."""
    names = flat_names(like)
    return jax.tree.unflatten(jax.tree.structure(like),
                              [flat[n] for n in names])
