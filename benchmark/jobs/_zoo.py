"""Glue shared by the job kinds that run a model of the program's zoo: the
reference's flat weights laid into the program's variable tree, by the rename
rules of the configuration's file."""

from __future__ import annotations

import importlib
import re

import jax
import jax.numpy as jnp


def reference_of(config: dict):
    return importlib.import_module("benchmark.reference." + config["reference"])


def _joined(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _rename(joined: str, rules):
    for pattern, repl in rules:
        if re.fullmatch(pattern, joined):
            return re.sub(pattern, repl, joined)
    raise KeyError(f"no rename rule matches the program's leaf {joined}")


def flatten(tree) -> dict:
    """{``/``-joined path: leaf}."""
    return {_joined(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def reference_names(params_tree, rules, prefix="params/") -> dict:
    """{path under the program's ``params``: the reference's name of that leaf}."""
    return {path: _rename(prefix + path, rules) for path in flatten(params_tree)}


def program_tree(ref_params: dict, abstract_tree, rules) -> dict:
    """The program's tree, every leaf taken from ``ref_params`` under the name
    the first matching rule gives its ``/``-joined path.  Running statistics the
    reference does not use (training) start at mean 0, variance 1.  A leaf no
    rule names, a shape that differs, or a reference weight left over raises."""
    used = set()

    def leaf(path, want):
        joined = _joined(path)
        name = _rename(joined, rules)
        if name not in ref_params:
            if joined.startswith("batch_stats/"):
                fill = jnp.ones if joined.endswith("/var") else jnp.zeros
                return fill(want.shape, want.dtype)
            raise KeyError(f"program leaf {joined} -> {name}: the reference has no such weight")
        value = ref_params[name]
        if tuple(value.shape) != tuple(want.shape):
            raise ValueError(f"{joined}: program {want.shape}, reference {name} {value.shape}")
        used.add(name)
        return value if value.dtype == want.dtype else value.astype(want.dtype)

    tree = jax.tree_util.tree_map_with_path(leaf, abstract_tree)
    left = sorted(set(ref_params) - used)
    if left:
        raise ValueError(f"reference weights the program has no leaf for: {left[:5]} ...")
    return tree
