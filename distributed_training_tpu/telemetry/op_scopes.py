"""Device time by the program's own layers: the scope vocabulary and
the map from HLO instruction to scope.

The serving programs put ``jax.named_scope`` around their parts
(``serving/engine.py::_scan_layers`` and ``_chunk_hidden``, the blocks'
``finish``, ``ops/paged_attention.py``, ``models/experts.py``), always
under one of the names in ``SCOPES``. A scope is metadata: it lands in
an instruction's ``op_name`` (``jit(serving_resident_decode)/dtt.engine/
while/body/dtt.attn.core/dot_general``) and changes nothing that runs.
A profile of a v5e does not carry ``op_name`` (an ``XLA Ops`` event is
the bare HLO instruction, ``%fusion.591 = ...``), and ``fusion.591`` is
a name the compiler gives anew with every edit of a program. So the
program writes the other half of the join itself: ``scope_map`` reads,
out of the optimized HLO text of a compiled program, which scope every
instruction that runs as an operation of its own lies in, and
``Engine.warmup`` emits it as one ``program_scopes`` record a program
(docs/observability.md). A trace's ``XLA Modules`` line says which
program an operation ran in, the record says which layer the operation
is: device time by layer (``perfbench/op_scopes.py`` is one such
reader).
"""

from __future__ import annotations

import contextlib
import re

# THE vocabulary: every ``jax.named_scope`` of the serving path is one
# of these, the innermost one an instruction lies in is its scope.
SCOPES = (
    "dtt.embed",         # token and position embedding
    "dtt.attn.project",  # norms, q / k / v and latent projections,
    #                      RoPE, the indexer's projections
    "dtt.kv.write",      # page coordinates of the new rows, the scatter
    "dtt.kv.read",       # cached rows on their way to the contraction:
    #                      table lookups, gathers, the layer's slice
    #                      out of the carried pool, re-laying copies
    "dtt.attn.select",   # the indexer's scores, the exact top-k, its mask
    "dtt.attn.core",     # logits, mask, softmax, weighted sum, latent
    #                      up-projections, the Pallas prefill kernels
    "dtt.attn.out",      # output projection, headwise gate
    "dtt.mlp",           # a dense feed-forward with its norm
    "dtt.moe.route",     # router product, top-k, gates
    "dtt.moe.experts",   # held experts' products, shared expert, combine
    "dtt.moe.shared",    # shared experts where a model names them apart
    #                      (inside ``dtt.moe.experts``: the innermost wins)
    "dtt.head",          # final norm, logits, sampling, the verify chain
    "dtt.engine",        # the programs' own bookkeeping: positions,
    #                      history rows, lengths, stop conditions,
    #                      counters, the loops that carry them
)
UNSCOPED = "_unscoped_"

# Opcodes that run as no operation of their own on the device: they
# name, regroup or point at buffers.
_NO_OPS = frozenset({"parameter", "constant", "tuple",
                     "get-tuple-element", "bitcast", "after-all"})
# ... and those that only rename or re-lay what a fusion computed: a
# fusion's root of such a kind does not say what the fusion is.
_RELAYS = _NO_OPS | {"reshape", "transpose", "copy", "broadcast"}
_HEADER = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|condition|body|to_apply|true_computation|"
    r"false_computation)=%?([^\s,)}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
# Whose called computations run as operations of their own; a fusion's,
# a reduction's or a sort's run inside the one operation.
_CONTROL = {"while": ("condition", "body"), "call": ("to_apply",),
            "conditional": ("true_computation", "false_computation")}


@contextlib.contextmanager
def own_metadata():
    """While open, JAX's persistent compile cache keys a program by its
    metadata too (``jax_compilation_cache_include_metadata_in_key``;
    restored on the way out). By default the key leaves debug info out,
    so a cache that another tree filled (an older commit's run on the
    same machine: the scopes are metadata, the program is the same)
    hands back that tree's executable, whose text names that tree's
    scopes, or none, and whose instructions are named after its
    ``op_name``s. A program compiled in here, the map's text and the
    executable that runs alike, carries this tree's."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


def scope_of(op_name: str) -> str | None:
    """The innermost ``dtt.*`` segment of an ``op_name`` path that is in
    the vocabulary, or None. An instruction the compiler merged from
    several carries their paths joined by ``;``, the later ones less
    the prefix they share with the first: the last path that names a
    scope decides."""
    for path in reversed(op_name.split(";")):
        for part in reversed(path.split("/")):
            if part in SCOPES:
                return part
    return None


def _opcode(rest: str) -> str:
    """The opcode of an instruction's right-hand side, ``<shape>
    <opcode>(<operands>), ...``; a tuple shape has spaces inside its
    brackets."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return rest.lstrip().partition("(")[0]


def _parse(hlo_text: str) -> tuple:
    """``(module name, entry computation, {computation: [(instruction,
    opcode, scope or None, {attribute: computation})]})``, a
    computation's instructions as printed: operands first, the root
    last."""
    module, entry, computations, at = None, None, {}, None
    for line in hlo_text.splitlines():
        if at is None:
            if module is None and (m := _HEADER.match(line)):
                module = m.group(1)
            elif m := _COMPUTATION.match(line):
                at = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            at = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        found = _OP_NAME.search(rest)
        called = {k: v for k, v in _CALLED.findall(rest)}
        if b := _BRANCHES.search(rest):
            called["branches"] = [c.strip().lstrip("%")
                                  for c in b.group(1).split(",")]
        at.append((name, _opcode(rest),
                   scope_of(found.group(1)) if found else None, called))
    return module, entry, computations


def scope_map(hlo_text: str) -> dict:
    """``{"module", "scopes": {scope: [instruction names]}, "mixed":
    [names], "instructions": n}`` of an optimized HLO module's text
    (``compiled.as_text()``).

    Every instruction of every computation that runs as an operation of
    its own is listed once: the entry's, ``while`` bodies' and
    conditions', called computations' and conditional branches', found
    from the entry down; not the insides of a fused computation, a
    reducer or a comparator, and not the instructions that run as
    nothing (parameters, constants, tuples and their elements,
    bitcasts). An instruction's scope is the innermost ``dtt.*``
    segment of its ``metadata={op_name=...}``. A fusion takes its root's
    scope, seen through a root that only renames or re-lays what the
    fusion computed (a bitcast, reshape, transpose, copy, broadcast or
    tuple: a projection whose root is the reshape of its consumer is
    the projection's): the scope of the last instruction
    before it that computes and names one, then of any that names one,
    the fusion's own ``op_name`` failing that. Where the instructions
    fused into it lie in more than one scope it is ALSO listed under
    ``mixed``. An instruction with no ``dtt.*`` segment (compiler-made
    copies, converts of parameters) goes under ``UNSCOPED``.
    ``instructions`` counts the listed ones."""
    module, entry, computations = _parse(hlo_text)
    scopes: dict = {}
    mixed: list = []
    seen: set = set()
    todo = [entry] if entry in computations else []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, opcode, scope, called in computations[comp]:
            for key in _CONTROL.get(opcode, ()):
                if called.get(key) in computations:
                    todo.append(called[key])
            if opcode == "conditional":
                todo.extend(c for c in called.get("branches", ())
                            if c in computations)
            if opcode in _NO_OPS:
                continue
            if opcode == "fusion" and called.get("calls") in computations:
                inside = computations[called["calls"]]
                within = [s for _n, _o, s, _c in inside if s]
                computed = [s for _n, o, s, _c in inside
                            if s and o not in _RELAYS]
                scope = (computed[-1:] or within[-1:] or [scope])[0]
                if len(set(within)) > 1:
                    mixed.append(name)
            scopes.setdefault(scope or UNSCOPED, []).append(name)
    return {"module": module, "scopes": scopes, "mixed": mixed,
            "instructions": sum(map(len, scopes.values()))}
