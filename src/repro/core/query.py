"""The ``async_query`` tagging primitive and QuerySpec registry.

This is the device-level analogue of the paper's ``executeQuery`` call: a
*query* is a parameterized, per-iteration data access (embedding gather,
KV fetch, remote parameter fetch, ...) that the loop-fission transformation
(Rule A, :mod:`repro.core.fission`) can pull out of a ``lax.scan`` and
execute in *batched* (set-oriented) form.

A model tags such an access by calling :func:`async_query` with a registered
:class:`QuerySpec`.  Untransformed programs behave exactly as if the query
were executed inline (the primitive's impl/lowering simply call
``spec.execute``), so tagging is semantically a no-op — precisely like the
paper's blocking ``executeQuery`` before transformation.  The fission pass
recognizes the primitive inside a scanned loop body, checks the Rule A
preconditions on the jaxpr data-dependence graph, and replaces the N
per-iteration executions with one call to ``spec.execute_batch``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import tree_util
from jax.extend import core as jex_core
from jax.interpreters import ad, batching, mlir

__all__ = [
    "QuerySpec",
    "register_query",
    "get_query_spec",
    "async_query",
    "async_query_p",
    "table_gather_spec",
    "sharded_param_fetch_spec",
]


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """Describes one batchable query type.

    Attributes:
      name: unique registry key.
      execute: the single-request (blocking) form, ``execute(*args)``.
        Must be a pure JAX function of its array arguments.
      execute_batch: the set-oriented form.  Receives every argument with a
        leading *batch* (loop-iteration) axis and must return the result
        with the same leading axis.  ``None`` falls back to
        ``jax.vmap(execute)`` — correct but without set-oriented savings.
      batch_axis_size_hint: optional static hint used by cost models.
    """

    name: str
    execute: Callable
    execute_batch: Optional[Callable] = None
    batch_axis_size_hint: Optional[int] = None

    def batched(self) -> Callable:
        if self.execute_batch is not None:
            return partial(self.execute_batch, batched=None)
        return jax.vmap(self.execute)


_REGISTRY: dict[str, QuerySpec] = {}


def register_query(spec: QuerySpec) -> QuerySpec:
    """Idempotently register ``spec`` under ``spec.name``."""
    existing = _REGISTRY.get(spec.name)
    if existing is not None and existing is not spec:
        # Re-registration with an identical definition is allowed (module
        # reloads in tests); silently replace.
        pass
    _REGISTRY[spec.name] = spec
    return spec


def get_query_spec(name: str) -> QuerySpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"No QuerySpec registered under {name!r}; call register_query first."
        ) from None


# ---------------------------------------------------------------------------
# The primitive.
#
# ``async_query_p`` is a real JAX primitive so that (a) it shows up as a
# single recognizable equation in the jaxpr (the analogue of the paper's
# query-execution *statement*), and (b) untransformed programs still trace,
# differentiate, vmap and lower correctly.
# ---------------------------------------------------------------------------

async_query_p = jex_core.Primitive("async_query")
async_query_p.multiple_results = True


def async_query(spec: QuerySpec | str, *args):
    """Tag a query execution point (paper: ``v = executeQuery(q)``).

    Semantically identical to ``spec.execute(*args)``.  Inside a loop that is
    later fissioned (Rule A) the execution is replaced by a single
    set-oriented ``spec.execute_batch`` call.
    """
    if isinstance(spec, QuerySpec):
        register_query(spec)
        name = spec.name
    else:
        name = spec
        spec = get_query_spec(name)
    flat_args, in_tree = tree_util.tree_flatten(args)
    out = async_query_p.bind(*flat_args, name=name, in_tree=in_tree)
    _, out_tree = _out_trees(spec, args)
    return tree_util.tree_unflatten(out_tree, out)


def _out_trees(spec: QuerySpec, args):
    """Abstractly evaluate ``spec.execute`` to get the output pytree."""
    shapes = jax.eval_shape(spec.execute, *args)
    flat, tree = tree_util.tree_flatten(shapes)
    return flat, tree


def _abstract_eval(*in_avals, name, in_tree):
    spec = get_query_spec(name)
    args = tree_util.tree_unflatten(
        in_tree, [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in in_avals]
    )
    out_shapes = jax.eval_shape(spec.execute, *args)
    flat, _ = tree_util.tree_flatten(out_shapes)
    return [jax.core.ShapedArray(s.shape, s.dtype) for s in flat]


async_query_p.def_abstract_eval(_abstract_eval)


def _run_execute(name, in_tree, *flat_args):
    spec = get_query_spec(name)
    args = tree_util.tree_unflatten(in_tree, list(flat_args))
    out = spec.execute(*args)
    flat, _ = tree_util.tree_flatten(out)
    return flat


def _impl(*flat_args, name, in_tree):
    return _run_execute(name, in_tree, *flat_args)


async_query_p.def_impl(_impl)

mlir.register_lowering(
    async_query_p,
    mlir.lower_fun(_impl, multiple_results=True),
)


def _jvp_rule(primals, tangents, *, name, in_tree):
    import numpy as np
    from jax import dtypes as _dtypes

    fn = partial(_run_execute, name, in_tree)

    def _zero_tan(p, t):
        if not isinstance(t, ad.Zero):
            return t
        aval = jax.typeof(p)
        if jnp.issubdtype(aval.dtype, jnp.inexact):
            return jnp.zeros(aval.shape, aval.dtype)
        return np.zeros(aval.shape, _dtypes.float0)  # int/bool primals

    tangents = [_zero_tan(p, t) for p, t in zip(primals, tangents)]
    return jax.jvp(fn, tuple(primals), tuple(tangents))


ad.primitive_jvps[async_query_p] = _jvp_rule


def _batch_rule(batched_args, batch_dims, *, name, in_tree):
    spec = get_query_spec(name)
    # Move every batched arg's batch axis to the front; broadcast the rest.
    size = None
    for a, d in zip(batched_args, batch_dims):
        if d is not batching.not_mapped:
            size = a.shape[d]
            break
    assert size is not None
    moved = []
    for a, d in zip(batched_args, batch_dims):
        if d is batching.not_mapped:
            moved.append(jnp.broadcast_to(a, (size,) + a.shape))
        else:
            moved.append(jnp.moveaxis(a, d, 0))
    args = tree_util.tree_unflatten(in_tree, moved)
    out = spec.batched()(*args)
    flat, _ = tree_util.tree_flatten(out)
    return flat, [0] * len(flat)


batching.primitive_batchers[async_query_p] = _batch_rule


# ---------------------------------------------------------------------------
# Built-in query specs
# ---------------------------------------------------------------------------


def _table_gather(table, ids):
    """Single query: select rows of ``table`` by integer key(s)."""
    return jnp.take(table, ids, axis=0)


def _table_gather_batch(table, ids, *, batched=None):
    """Set-oriented form: ONE gather over all iterations' keys.

    Fission's calling convention: loop-invariant arguments (the table)
    arrive *unstacked*, varying arguments (the ids) arrive with a leading
    loop axis; ``batched`` is the per-leaf mask.  The whole batch becomes a
    single flat gather — the device analogue of the paper's rewritten
    set-oriented query: on TPU, one large DMA-friendly gather instead of N
    scalar-driven small ones inside a sequential scan.
    """
    if batched is not None and batched[0]:
        # Degenerate case: a varying table (one per iteration); vmap it.
        return jax.vmap(_table_gather)(table, ids)
    flat = ids.reshape(-1)
    rows = jnp.take(table, flat, axis=0)
    return rows.reshape(ids.shape + table.shape[1:])


table_gather_spec = register_query(
    QuerySpec(
        name="table_gather",
        execute=_table_gather,
        execute_batch=_table_gather_batch,
    )
)


def _sharded_param_fetch(param_shard, _token):
    """Single query: fetch one (sharded) parameter — stands for the remote
    parameter/KV fetch; the batched form coalesces N fetches into one."""
    return param_shard


def _sharded_param_fetch_batch(param_shard, _tokens, *, batched=None):
    return param_shard


sharded_param_fetch_spec = register_query(
    QuerySpec(
        name="sharded_param_fetch",
        execute=_sharded_param_fetch,
        execute_batch=_sharded_param_fetch_batch,
    )
)
