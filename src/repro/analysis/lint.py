"""Repo-discipline linter: AST rules for the decentralized-training stack.

Generic linters don't know that a ``float()`` inside a jitted train step is a
trace-time crash, or that a new ``CommState`` field silently breaks old
checkpoints.  These rules encode the repo's own discipline:

  RPR001  Python ``if``/``while`` branching on a traced value inside a
          traced region (step/mix functions).  Branch on static config
          (``self.period``), not on array values — use ``lax.cond``.
  RPR002  Host materialization of a traced value in a traced region:
          ``float()`` / ``int()`` / ``bool()`` / ``.item()`` /
          ``np.asarray()`` / ``np.array()`` on something derived from a
          traced argument.  These sync the device or crash under jit.
  RPR003  A ``Mixer`` subclass whose ``init_state`` populates a non-trivial
          ``CommState`` field without a ``state_specs`` (own or inherited
          in-module) declaring that field's partitioning — the field would
          silently fall back to the trivial spec under pjit.
  RPR004  Device allocation at import time: module-level ``jnp.*`` /
          ``jax.random.*`` / ``jax.device_put`` / ``jax.devices`` calls.
          They pin the backend before the entry point can configure it
          (e.g. ``XLA_FLAGS`` host-device counts).
  RPR005  CommState schema discipline: every field of the NamedTuple must
          be registered in the checkpoint zero-padding table
          (``repro.checkpoint.io.COMM_STATE_PAD``) and carry a default in
          the class; and ``CommState(...)`` may only be constructed in the
          protocol module or inside ``init_state``/``state_specs`` hooks —
          everywhere else use ``state._replace(...)`` so adding a field
          cannot silently drop it.
  RPR006  Host callback (``io_callback`` / ``pure_callback``) staged
          outside ``repro.obs``.  The telemetry sink is the ONE sanctioned
          host-callback path (packed payloads, measured overhead budget,
          ``audit_host_callbacks`` allow-list); ad-hoc callbacks elsewhere
          silently serialize the device stream and dodge the budget.
  RPR007  Wire-layer state discipline (the Topology × Transport × Wire
          stack): a ``*Wire`` class whose ``init_fields`` populates a
          non-trivial ``CommState`` field without a ``spec_fields`` (own
          or inherited in-module) declaring its partitioning — the layer
          twin of RPR003 (``ComposedMixer`` splices the wire's dicts into
          the state, so a missing spec falls back to the trivial one
          under pjit).

Suppression: append ``# repro: noqa`` (all rules) or
``# repro: noqa[RPR002]`` (specific rules) to the flagged line, with a
justification nearby.

Traced regions are found statically: ``__call__``/``_mix``/``mix_tree``
methods of Mixer classes (those deriving from ``Mixer`` or
``ComposedMixer``), the traced layer methods of ``*Topology`` /
``*Transport`` / ``*Wire`` classes (``round_w``; ``apply_w``/``apply``/
``node_index``; ``encode_leaf``/``compress_block``/``rate``/
``next_sched_state``/``round_wire_bits``/``gamma_for``), functions named
``train_step``/``eval_step``, functions passed by name to ``jit``/``scan``/
``cond``/``while_loop``/``vmap``/``pmap``/``shard_map``/``checkify``,
nested ``def``s inside those, and (one fixed point) any same-module
function or ``self.`` method they call.

Run it: ``python -m repro.analysis [paths...]`` (exits 1 on findings).
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re

_TRACED_SEED_METHODS = {"__call__", "_mix", "mix_tree"}
_TRACED_SEED_NAMES = {"train_step", "eval_step"}
# consensus-layer classes (matched by name suffix) and the methods of each
# that run under tracing — ComposedMixer calls them from its round bodies
_LAYER_TRACED_METHODS = {
    "Topology": {"round_w"},
    "Transport": {"apply_w", "apply", "node_index"},
    "Wire": {"encode_leaf", "compress_block", "rate", "next_sched_state",
             "round_wire_bits", "gamma_for"},
}
_TRACING_CALLS = {"jit", "scan", "cond", "while_loop", "fori_loop", "vmap",
                  "pmap", "shard_map", "checkify", "value_and_grad", "grad",
                  "switch", "remat", "checkpoint"}
# CommState fields whose trivial spec (fully replicated scalar/empty) is
# always right — populating them in init_state needs no state_specs entry
_TRIVIAL_SPEC_FIELDS = {"key", "rounds", "wire_bits", "res_norm", "res_ref",
                        "ef_rounds", "ef_drift"}
# where CommState(...) construction is legitimate
_COMMSTATE_CTOR_FNS = {"init_state", "state_specs", "trivial_comm_state",
                       "trivial_state_specs", "_pad_comm_fields",
                       "restore_train_state"}
_HOST_CASTS = {"float", "int", "bool"}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize"}
_STATIC_CALLS = {"isinstance", "hasattr", "getattr", "len", "callable",
                 "issubclass", "type"}

_NOQA_RE = re.compile(r"#\s*repro:\s*noqa(?:\[([A-Z0-9, ]+)\])?")


@dataclasses.dataclass(frozen=True)
class LintFinding:
    path: str
    line: int
    code: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"


def _noqa_map(source: str) -> dict[int, set[str] | None]:
    """line -> suppressed codes (None = all) from ``# repro: noqa`` marks."""
    out: dict[int, set[str] | None] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _NOQA_RE.search(line)
        if not m:
            continue
        codes = m.group(1)
        out[i] = (None if codes is None
                  else {c.strip() for c in codes.split(",") if c.strip()})
    return out


def _attr_chain(node) -> list[str]:
    """a.b.c -> ["a", "b", "c"]; [] when the root is not a plain Name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return []


def _call_name(node: ast.Call) -> str:
    """Last path component of the called object ("jax.lax.cond" -> "cond")."""
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


class _TaintWalker(ast.NodeVisitor):
    """Collect Name ids that (syntactically) carry traced values, skipping
    statically-evaluated subtrees (isinstance/len/shape/... and `is None`)."""

    def __init__(self, tainted: set[str]):
        self.tainted = tainted
        self.hits: list[str] = []

    def visit_Call(self, node: ast.Call):
        if _call_name(node) in _STATIC_CALLS:
            return  # evaluated at trace time
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return  # x.shape / x.dtype are static under tracing
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return  # `x is None` — identity on the python value
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name):
        if node.id in self.tainted:
            self.hits.append(node.id)


def _traced_names_in(node, tainted: set[str]) -> list[str]:
    w = _TaintWalker(tainted)
    w.visit(node)
    return w.hits


def _function_index(tree: ast.Module):
    """(module_fns, classes) where classes -> {name: (node, {method: fn})}."""
    module_fns: dict[str, ast.FunctionDef] = {}
    classes: dict[str, tuple[ast.ClassDef, dict]] = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            module_fns[node.name] = node
        elif isinstance(node, ast.ClassDef):
            methods = {n.name: n for n in node.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            classes[node.name] = (node, methods)
    return module_fns, classes


# the consensus-protocol bases: a class deriving from one of them (by name,
# directly or through classes of the same module) is a mixer — the wrappers
# (RepeatMixer, LocalUpdateMixer) and ComposedMixer included, whatever the
# subclass is called
_MIXER_BASES = {"Mixer", "ComposedMixer"}


def _is_mixer_class(cls: ast.ClassDef, classes: dict,
                    seen: frozenset = frozenset()) -> bool:
    if cls.name in _MIXER_BASES:
        return True
    for base in cls.bases:
        chain = _attr_chain(base)
        name = chain[-1] if chain else ""
        if name in _MIXER_BASES:
            return True
        if (name in classes and name not in seen
                and _is_mixer_class(classes[name][0], classes,
                                    seen | {cls.name})):
            return True
    return False


def _find_traced_functions(tree: ast.Module):
    """Set of FunctionDef nodes considered traced regions (see module doc)."""
    module_fns, classes = _function_index(tree)
    traced: set[ast.AST] = set()

    for cls_name, (cls, methods) in classes.items():
        if _is_mixer_class(cls, classes):
            for m in _TRACED_SEED_METHODS:
                if m in methods:
                    traced.add(methods[m])
        for suffix, layer_methods in _LAYER_TRACED_METHODS.items():
            if cls_name.endswith(suffix):
                for m in layer_methods:
                    if m in methods:
                        traced.add(methods[m])
    for name, fn in module_fns.items():
        if name in _TRACED_SEED_NAMES:
            traced.add(fn)
    # nested defs named like a step inside builders (build_train_step)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in _TRACED_SEED_NAMES:
            traced.add(node)
    # functions passed by name into tracing transforms
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _call_name(node) not in _TRACING_CALLS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name) and arg.id in module_fns:
                traced.add(module_fns[arg.id])

    # fixed point: nested defs + same-module / self. calls from traced fns
    changed = True
    while changed:
        changed = False
        for fn in list(traced):
            for node in ast.walk(fn):
                if node is not fn and isinstance(
                        node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if node not in traced:
                        traced.add(node)
                        changed = True
                if isinstance(node, ast.Call):
                    callee = None
                    f = node.func
                    if isinstance(f, ast.Name) and f.id in module_fns:
                        callee = module_fns[f.id]
                    elif (isinstance(f, ast.Attribute)
                          and isinstance(f.value, ast.Name)
                          and f.value.id == "self"):
                        for _, (cls, methods) in classes.items():
                            if fn in methods.values() and f.attr in methods:
                                callee = methods[f.attr]
                                break
                    if callee is not None and callee not in traced:
                        traced.add(callee)
                        changed = True
    return traced


def _taint_set(fn) -> set[str]:
    """Traced-value names inside one traced function: its parameters (minus
    self/cls) plus locals assigned from tainted expressions."""
    args = fn.args
    names = {a.arg for a in
             (args.posonlyargs + args.args + args.kwonlyargs)}
    for a in (args.vararg, args.kwarg):
        if a is not None:
            names.add(a.arg)
    names -= {"self", "cls"}
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _traced_names_in(
                    node.value, names):
                for tgt in node.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name) and n.id not in names:
                            names.add(n.id)
                            changed = True
    return names


def _lint_traced_fn(fn, path: str, findings: list[LintFinding]) -> None:
    tainted = _taint_set(fn)
    nested = {n for n in ast.walk(fn)
              if n is not fn
              and isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}

    def owned(node):
        # skip statements inside nested defs — they are linted as their own
        # traced functions (with their own parameter taint)
        for sub in nested:
            if (sub.lineno <= node.lineno
                    and node.lineno <= (sub.end_lineno or sub.lineno)):
                return False
        return True

    for node in ast.walk(fn):
        if isinstance(node, (ast.If, ast.While)) and owned(node):
            hits = _traced_names_in(node.test, tainted)
            if hits:
                kw = "while" if isinstance(node, ast.While) else "if"
                findings.append(LintFinding(
                    path, node.lineno, "RPR001",
                    f"python `{kw}` on traced value(s) "
                    f"{sorted(set(hits))} inside traced function "
                    f"`{fn.name}` — use jax.lax.cond/select"))
        if isinstance(node, ast.Call) and owned(node):
            name = _call_name(node)
            chain = _attr_chain(node.func)
            is_np_cast = (len(chain) >= 2 and chain[0] in ("np", "numpy")
                          and chain[-1] in ("asarray", "array"))
            is_host_cast = (isinstance(node.func, ast.Name)
                            and name in _HOST_CASTS)
            is_item = (isinstance(node.func, ast.Attribute)
                       and node.func.attr == "item")
            if not (is_np_cast or is_host_cast or is_item):
                continue
            probe = (node.func.value if is_item
                     else ast.Tuple(elts=list(node.args), ctx=ast.Load()))
            hits = _traced_names_in(probe, tainted)
            if hits:
                what = ".item()" if is_item else f"{name}()"
                findings.append(LintFinding(
                    path, node.lineno, "RPR002",
                    f"host materialization {what} of traced value(s) "
                    f"{sorted(set(hits))} inside traced function "
                    f"`{fn.name}` — crashes or syncs under jit"))


def _commstate_fields_set(fn) -> set[str]:
    """CommState field names populated by _replace/CommState calls in fn."""
    fields: set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        is_replace = isinstance(f, ast.Attribute) and f.attr == "_replace"
        is_ctor = (_call_name(node) == "CommState")
        if is_replace or is_ctor:
            fields |= {kw.arg for kw in node.keywords if kw.arg}
    return fields


def _lint_mixer_protocol(tree: ast.Module, path: str,
                         findings: list[LintFinding]) -> None:
    """RPR003: init_state populates a non-trivial field, no spec declares it."""
    _, classes = _function_index(tree)

    def spec_fields(cls_name: str, seen: set[str]) -> set[str]:
        if cls_name not in classes or cls_name in seen:
            return set()
        seen.add(cls_name)
        cls, methods = classes[cls_name]
        out: set[str] = set()
        if "state_specs" in methods:
            out |= _commstate_fields_set(methods["state_specs"])
        for base in cls.bases:
            chain = _attr_chain(base)
            if chain:
                out |= spec_fields(chain[-1], seen)
        return out

    for cls_name, (cls, methods) in classes.items():
        if not _is_mixer_class(cls, classes) or "init_state" not in methods:
            continue
        interesting = (_commstate_fields_set(methods["init_state"])
                       - _TRIVIAL_SPEC_FIELDS)
        if not interesting:
            continue
        declared = spec_fields(cls_name, set())
        # an inherited out-of-module state_specs is invisible here; only
        # flag when the class hierarchy in this module declares specs for
        # SOME fields but not these (a partial spec is the real hazard)
        missing = interesting - declared
        if missing and declared:
            findings.append(LintFinding(
                path, methods["init_state"].lineno, "RPR003",
                f"{cls_name}.init_state populates CommState field(s) "
                f"{sorted(missing)} but no state_specs in its (in-module) "
                "hierarchy declares their partitioning"))


def _dict_string_keys(fn) -> set[str]:
    """String keys a function populates into dict literals or via
    ``fields["name"] = ...`` subscript assignment."""
    keys: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Dict):
            keys |= {k.value for k in node.keys
                     if isinstance(k, ast.Constant)
                     and isinstance(k.value, str)}
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.slice, ast.Constant)
                        and isinstance(tgt.slice.value, str)):
                    keys.add(tgt.slice.value)
    return keys


def _lint_wire_state_discipline(tree: ast.Module, path: str,
                                findings: list[LintFinding]) -> None:
    """RPR007: a wire's init_fields owns a non-trivial CommState field that
    no spec_fields in its (in-module) hierarchy declares."""
    _, classes = _function_index(tree)

    def spec_keys(cls_name: str, seen: set[str]) -> set[str]:
        if cls_name not in classes or cls_name in seen:
            return set()
        seen.add(cls_name)
        cls, methods = classes[cls_name]
        out: set[str] = set()
        if "spec_fields" in methods:
            out |= _dict_string_keys(methods["spec_fields"])
        for base in cls.bases:
            chain = _attr_chain(base)
            if chain:
                out |= spec_keys(chain[-1], seen)
        return out

    for cls_name, (cls, methods) in classes.items():
        if not cls_name.endswith("Wire") or "init_fields" not in methods:
            continue
        interesting = (_dict_string_keys(methods["init_fields"])
                       - _TRIVIAL_SPEC_FIELDS)
        if not interesting:
            continue
        missing = interesting - spec_keys(cls_name, set())
        if missing:
            findings.append(LintFinding(
                path, methods["init_fields"].lineno, "RPR007",
                f"{cls_name}.init_fields populates CommState field(s) "
                f"{sorted(missing)} but no spec_fields in its (in-module) "
                "hierarchy declares their partitioning"))


def _lint_import_time_device(tree: ast.Module, path: str,
                             findings: list[LintFinding]) -> None:
    """RPR004: jnp/jax.random/device_put calls at module import time."""

    def check_expr(node):
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            chain = _attr_chain(call.func)
            if not chain:
                continue
            root = chain[0]
            bad = (root == "jnp"
                   or (root == "jax" and len(chain) >= 2
                       and chain[1] in ("numpy", "random", "device_put",
                                        "devices", "local_devices")))
            if bad:
                findings.append(LintFinding(
                    path, call.lineno, "RPR004",
                    f"device allocation at import time: "
                    f"{'.'.join(chain)}() in module scope — initializes "
                    "the backend before entry points can configure it"))

    for node in tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.Expr)):
            check_expr(node)


def _lint_commstate_ctor(tree: ast.Module, path: str,
                         findings: list[LintFinding]) -> None:
    """RPR005 (per-file half): CommState(...) outside the allowed hooks."""
    if os.path.basename(path) == "protocol.py":
        return
    allowed_spans: list[tuple[int, int]] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in _COMMSTATE_CTOR_FNS:
            allowed_spans.append((node.lineno, node.end_lineno or node.lineno))
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) == "CommState"):
            continue
        if any(a <= node.lineno <= b for a, b in allowed_spans):
            continue
        findings.append(LintFinding(
            path, node.lineno, "RPR005",
            "CommState(...) constructed outside init_state/state_specs — "
            "use state._replace(...) so new fields cannot be dropped"))


_CALLBACK_CALLS = {"io_callback", "pure_callback"}


def _lint_host_callbacks(tree: ast.Module, path: str,
                         findings: list[LintFinding]) -> None:
    """RPR006: io_callback/pure_callback staged outside repro.obs."""
    norm = path.replace(os.sep, "/")
    if "repro/obs/" in norm:
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and _call_name(node) in _CALLBACK_CALLS:
            findings.append(LintFinding(
                path, node.lineno, "RPR006",
                f"host callback {_call_name(node)}() outside repro.obs — "
                "route host taps through the MetricsSink (the one "
                "budgeted, audited callback path)"))


def lint_source(source: str, path: str = "<string>") -> list[LintFinding]:
    """All single-file findings for one module's source text."""
    tree = ast.parse(source)
    findings: list[LintFinding] = []
    for fn in _find_traced_functions(tree):
        _lint_traced_fn(fn, path, findings)
    _lint_mixer_protocol(tree, path, findings)
    _lint_wire_state_discipline(tree, path, findings)
    _lint_import_time_device(tree, path, findings)
    _lint_commstate_ctor(tree, path, findings)
    _lint_host_callbacks(tree, path, findings)
    noqa = _noqa_map(source)
    kept = []
    for f in findings:
        codes = noqa.get(f.line, ...)
        if codes is ... :
            kept.append(f)
        elif codes is not None and f.code not in codes:
            kept.append(f)
    return sorted(kept, key=lambda f: (f.path, f.line, f.code))


def _parse_commstate_fields(protocol_src: str) -> list[str]:
    tree = ast.parse(protocol_src)
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "CommState":
            return [n.target.id for n in node.body
                    if isinstance(n, ast.AnnAssign)
                    and isinstance(n.target, ast.Name)]
    return []


def _parse_pad_table(io_src: str) -> list[str] | None:
    tree = ast.parse(io_src)
    for node in tree.body:
        if isinstance(node, ast.Assign):
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            if "COMM_STATE_PAD" in names and isinstance(node.value, ast.Dict):
                return [k.value for k in node.value.keys
                        if isinstance(k, ast.Constant)]
    return None


def lint_schema(protocol_path: str, io_path: str) -> list[LintFinding]:
    """RPR005 (cross-file half): CommState fields vs the checkpoint pad table."""
    findings: list[LintFinding] = []
    with open(protocol_path) as f:
        fields = _parse_commstate_fields(f.read())
    with open(io_path) as f:
        pad = _parse_pad_table(f.read())
    if pad is None:
        findings.append(LintFinding(
            io_path, 1, "RPR005",
            "COMM_STATE_PAD table not found — checkpoint restore cannot "
            "zero-pad CommState fields from older runs"))
        return findings
    for field in fields:
        if field not in pad:
            findings.append(LintFinding(
                protocol_path, 1, "RPR005",
                f"CommState field {field!r} missing from the checkpoint "
                "zero-padding table (repro.checkpoint.io.COMM_STATE_PAD) — "
                "old checkpoints would fail to restore"))
    for field in pad:
        if field not in fields:
            findings.append(LintFinding(
                io_path, 1, "RPR005",
                f"COMM_STATE_PAD entry {field!r} is not a CommState field "
                "(stale table?)"))
    return findings


def _iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, _dirs, files in os.walk(p):
                if "__pycache__" in root:
                    continue
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def lint_paths(paths) -> list[LintFinding]:
    """Lint every .py under ``paths``; adds the cross-file schema check when
    the protocol and checkpoint modules are both in scope."""
    findings: list[LintFinding] = []
    protocol_path = io_path = None
    for path in _iter_py_files(paths):
        with open(path) as f:
            src = f.read()
        try:
            findings.extend(lint_source(src, path))
        except SyntaxError as e:
            findings.append(LintFinding(
                path, e.lineno or 1, "RPR000", f"syntax error: {e.msg}"))
        norm = path.replace(os.sep, "/")
        if norm.endswith("repro/comm/protocol.py"):
            protocol_path = path
        if norm.endswith("repro/checkpoint/io.py"):
            io_path = path
    if protocol_path and io_path:
        findings.extend(lint_schema(protocol_path, io_path))
    return findings


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repo-discipline linter (rules RPR001-RPR007)")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to lint (default: src/ or .)")
    args = ap.parse_args(argv)
    paths = args.paths or (["src"] if os.path.isdir("src") else ["."])
    findings = lint_paths(paths)
    for f in findings:
        print(f)
    if findings:
        print(f"{len(findings)} finding(s)")
        return 1
    print("repro.analysis.lint: clean")
    return 0
