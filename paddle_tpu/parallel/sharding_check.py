"""Compiled-HLO sharding assertions.

Real multi-chip hardware is unavailable to CI, so the compiled module is
the only multi-chip *performance* signal: these checks parse the
optimized HLO of a mesh-compiled train step (``Executor.lowered_hlo_text``)
and assert structural sharding quality — the reference analog is
``multi_devices_graph_check_pass.cc`` asserting SSA-graph structure.

The post-SPMD entry computation carries, per parameter, the LOCAL shape,
a ``sharding={...}`` annotation, and ``metadata={op_name="state['<var>']"}``
— both checks key off those.
"""

import re

__all__ = ["assert_no_param_allgather", "assert_param_sharded",
           "entry_param_shardings", "collect_allgather_shapes",
           "collect_jaxpr_collectives", "assert_no_full_output_psum"]

_SHAPE_RE = re.compile(r"=\s*\(?[a-z0-9]+\[([0-9,]*)\]")


def _shape_of(line):
    m = _SHAPE_RE.search(line)
    if not m or not m.group(1):
        return None
    return tuple(int(d) for d in m.group(1).split(","))


def entry_param_shardings(hlo_text):
    """{state var name: (local_shape, sharding str)} for entry params."""
    m = re.search(r"ENTRY [^\{]*\{(.*?)\n\}", hlo_text, re.S)
    entry = m.group(1) if m else hlo_text
    out = {}
    for line in entry.splitlines():
        ls = line.strip()
        if " parameter(" not in ls:
            continue
        nm = re.search(r"op_name=\"state\[\\?'([^'\\\"]+)", ls)
        if not nm:
            continue
        sh = re.search(r"sharding=\{([^}]*)\}", ls)
        out[nm.group(1)] = (_shape_of(ls), sh.group(1) if sh else "")
    return out


def _is_sharded(sharding):
    """True iff the annotation actually splits a tensor dimension."""
    m = re.search(r"devices=\[([0-9,]+)\]", sharding)
    if not m:
        return False
    dims = [int(d) for d in m.group(1).split(",")]
    if "last_tile_dim_replicate" in sharding:
        dims = dims[:-1]
    return any(d > 1 for d in dims)


def collect_allgather_shapes(hlo_text):
    """Result shapes of every all-gather instruction.

    Async ``all-gather-start`` results are ``(operand_shard, result)``
    tuples — take the LAST shape in the tuple (the gathered result), not
    the first (the pre-gather shard)."""
    shapes = []
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        if not (re.match(r"%?all-gather[\w.\-]* =", ls) or (
                " = " in ls and ("all-gather(" in ls
                                 or "all-gather-start(" in ls))):
            continue
        lhs = ls.split(" = ", 1)[-1]
        lhs = lhs.split("all-gather", 1)[0]  # the result type only
        tup = re.findall(r"[a-z0-9]+\[([0-9,]*)\]", lhs)
        if tup and tup[-1]:
            shapes.append(tuple(int(d) for d in tup[-1].split(",")))
    return shapes


_COLLECTIVE_PRIMS = ("psum", "all_to_all", "all_gather", "psum_scatter",
                     "ppermute", "all_gather_invariant")
# under shard_map's varying-axes check a psum of a per-shard (varying)
# value appears in the jaxpr as "psum_invariant" — report it under the
# canonical name
_PRIM_ALIASES = {"psum_invariant": "psum"}


def collect_jaxpr_collectives(jaxpr):
    """[(primitive_name, axes, [out shapes...])] for every named-axis
    collective anywhere in a (Closed)Jaxpr, recursing into sub-jaxprs
    (shard_map bodies, cond branches, scan/while bodies, pjit calls).

    The jaxpr view is the right layer for the ISSUE 13 psum audit: a
    psum primitive can ONLY enter the program through an explicit
    ``jax.lax.psum`` inside a shard_map body (GSPMD's implicit
    collectives appear later, in the HLO), so a [n, D] psum here IS the
    psum-of-partials lookup formulation, with no replica-group parsing
    or shape-coincidence heuristics."""
    closed = getattr(jaxpr, "jaxpr", jaxpr)
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            name = _PRIM_ALIASES.get(eqn.primitive.name,
                                     eqn.primitive.name)
            if name in _COLLECTIVE_PRIMS:
                axes = eqn.params.get("axes",
                                      eqn.params.get("axis_name"))
                shapes = [tuple(getattr(v.aval, "shape", ()))
                          for v in eqn.outvars]
                found.append((name, axes, shapes))
            for sub in _subjaxprs(eqn.params):
                walk(sub)

    def _subjaxprs(params):
        for v in params.values():
            for sub in _as_jaxprs(v):
                yield sub

    def _as_jaxprs(v):
        if hasattr(v, "eqns"):                      # Jaxpr
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr                           # ClosedJaxpr
        elif isinstance(v, (list, tuple)):
            for item in v:
                yield from _as_jaxprs(item)

    walk(closed)
    return found


def assert_no_full_output_psum(collectives, width):
    """ISSUE 13 dryrun stage: the id-routed sharded-embedding step must
    not reduce a full lookup output. In the jaxpr (see
    :func:`collect_jaxpr_collectives`) the psum-of-partials formulation
    is a ``psum`` of a >=2-D tensor with last dim = ``width`` (the table
    row width); the routed path has none — its collectives are
    ``all_to_all`` (+ the output-replication ``all_gather``)."""
    bad = [(name, axes, s)
           for name, axes, shapes in collectives if name == "psum"
           for s in shapes if len(s) >= 2 and s[-1] == width]
    assert not bad, (
        "sharded-embedding step psums full [n, %d] lookup outputs %s — "
        "the psum-of-partials formulation leaked onto the all-to-all "
        "path (O(mp*n*D) redundant ICI volume; "
        "parallel/sharded_embedding.py)" % (width, bad))


def assert_no_param_allgather(hlo_text, param_shapes):
    """No all-gather result may materialize a full (>=2-D) parameter.

    ``param_shapes``: LOGICAL parameter shape tuples (an all-gather
    reassembling a parameter produces its full logical shape). 1-D
    shapes are skipped (biases collide with activation vectors)."""
    params = {tuple(int(x) for x in s) for s in param_shapes
              if len(tuple(s)) >= 2}
    bad = [s for s in collect_allgather_shapes(hlo_text) if s in params]
    assert not bad, (
        "steady-state data-parallel step all-gathers full parameter "
        "tensors %s — parameters should stay resident, only gradient "
        "reductions belong in the step" % bad)


def assert_param_sharded(hlo_text, var_name, logical_shape=None):
    """The entry parameter for state var ``var_name`` must be actually
    sharded: non-replicated annotation AND (when ``logical_shape`` is
    given) a strictly smaller local shape."""
    params = entry_param_shardings(hlo_text)
    assert var_name in params, (
        "state var %r not found among entry parameters (have %d: %s...)"
        % (var_name, len(params), sorted(params)[:5]))
    local, sharding = params[var_name]
    assert _is_sharded(sharding), (
        "param %r is not sharded (sharding=%r)" % (var_name, sharding))
    if logical_shape is not None and local is not None:
        full = 1
        for d in logical_shape:
            full *= d
        loc = 1
        for d in local:
            loc *= d
        assert loc < full, (
            "param %r local shape %s is not smaller than logical %s"
            % (var_name, local, tuple(logical_shape)))
