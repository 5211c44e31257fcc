"""The packers' per-item loops index their carried state by one-hot selects.

Under the fleet's ``jax.vmap`` a read ``x[i]`` or write ``x.at[i].set`` with
a traced ``i`` becomes a batched gather or scatter, a slow serialized op on
TPU, run on every iteration of every packer scan.  Compiled under ``vmap``,
no ``while`` body of a registered jax packer may hold either.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.launch.hlo_walker import _called_comps, parse_module
from repro.registry import PACKER_FAMILIES, list_policies, packer_for

ALGORITHMS = list_policies(family=PACKER_FAMILIES, backend="jax")
B, N = 4, 16


def _loop_op_kinds(hlo: str) -> dict:
    """Opcodes of every computation reachable from a ``while`` body (fused
    computations included), keyed by computation name."""
    comps = parse_module(hlo)
    todo = [re.search(r"body=%?([\w.\-]+)", op.line).group(1)
            for comp in comps.values() for op in comp.ops.values()
            if op.kind == "while"]
    kinds = {}
    while todo:
        name = todo.pop()
        if name in kinds or name not in comps:
            continue
        kinds[name] = {op.kind for op in comps[name].ops.values()}
        for op in comps[name].ops.values():
            todo.extend(_called_comps(op))
    return kinds


def _batched_hlo(fn) -> str:
    args = (jax.ShapeDtypeStruct((B, N), jnp.float32),
            jax.ShapeDtypeStruct((B, N), jnp.int32),
            jax.ShapeDtypeStruct((B, N), jnp.bool_))
    return jax.jit(jax.vmap(fn)).lower(*args).compile().as_text()


def _indexed_in_loops(kinds: dict) -> dict:
    return {name: sorted(ks & {"scatter", "gather"})
            for name, ks in kinds.items() if ks & {"scatter", "gather"}}


def test_detector_sees_a_batched_scatter():
    """The check below cannot pass vacuously: a vmapped scan that writes
    its carry at a traced index shows a scatter inside its loop."""
    def traced_index_write(speeds, prev, active):
        def body(acc, j):
            i = jnp.clip(prev[j], 0, N - 1)
            return acc.at[i].add(jnp.where(active[j], speeds[j], 0.0)), None
        acc, _ = lax.scan(body, jnp.zeros(N, jnp.float32), jnp.arange(N))
        return acc

    kinds = _loop_op_kinds(_batched_hlo(traced_index_write))
    assert kinds, "no while body found"
    assert _indexed_in_loops(kinds)


@pytest.mark.parametrize("name", sorted(ALGORITHMS))
def test_packer_loops_hold_no_scatter_or_gather(name):
    fn = packer_for(name, backend="jax")
    kinds = _loop_op_kinds(
        _batched_hlo(lambda s, p, a: fn(s, p, 1.0, active=a)))
    assert kinds, f"{name}: no while body found"
    assert not _indexed_in_loops(kinds), (
        f"{name}: traced-index ops inside the item loop: "
        f"{_indexed_in_loops(kinds)}")
