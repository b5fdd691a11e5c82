"""Metric-name lint (tier-1): every `biscotti_*` metric family emitted
anywhere in the package appears in docs/OBSERVABILITY.md with a matching
name and label set — and vice versa, no documented-but-dead rows. The
doc table is the contract the obs tooling and downstream dashboards are
built against; this test is what keeps it true as PRs add planes.

The scanner is AST-based: family names come from the first argument of
`*.counter/gauge/histogram(...)` calls (literals, or module-level
string constants resolved across the package — the `WIRE_BYTES_METRIC`
pattern); label keys come from the keyword arguments of the
`.inc/.set/.observe(...)` call sites reached from each family, both
chained (`reg.counter(N).inc(k=v)`) and through a local variable
(`g = reg.gauge(N); g.set(v, k=v)`). A family may also be DECLARED where
its value is computed and set by a loop that names none (PR 46): a tuple
literal that opens with the family's name, `(name, help)` in
ops/moe.py's `GAUGES` and `(name, help, value, {label: ...})` in the rows
a language model declares as `info["gauges"]` (models/lm.py:
`attention_gauges`, and each model's builder); the label keys are the dict
literal's."""

import ast
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = REPO / "biscotti_tpu"
DOC = REPO / "docs" / "OBSERVABILITY.md"

_NAME_RX = re.compile(r"^biscotti_[a-z0-9_]+$")
_UPDATE_METHODS = {"inc", "set", "observe"}
_FAMILY_METHODS = {"counter", "gauge", "histogram"}

# families whose emission is data-driven and not statically visible, or
# whose label keys the scanner cannot resolve — currently none; add a
# name here (with a comment why) if a legitimately dynamic family ever
# appears, rather than weakening the scanner
SCAN_EXEMPT: set = set()


def _source_files():
    yield from sorted(PACKAGE.rglob("*.py"))
    yield REPO / "bench.py"  # bench families are documented too


def _collect_constants():
    """{identifier: value} for every module-level `NAME = "biscotti_…"`
    assignment in the scanned files — resolves both `NAME` references
    and `module.NAME` attributes (matched on the attribute name)."""
    consts = {}
    for path in _source_files():
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str) \
                    and _NAME_RX.match(node.value.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        consts[tgt.id] = node.value.value
    return consts


def _resolve_name(node, consts):
    """The metric-family name of a counter/gauge/histogram call's first
    argument, or None when it is not statically resolvable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if _NAME_RX.match(node.value) else None
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    if isinstance(node, ast.Attribute):
        return consts.get(node.attr)
    return None


def _family_call_name(call, consts):
    """`call` is an ast.Call; returns the family name when it is a
    counter/gauge/histogram(...) accessor call."""
    if isinstance(call, ast.Call) and isinstance(call.func, ast.Attribute) \
            and call.func.attr in _FAMILY_METHODS and call.args:
        return _resolve_name(call.args[0], consts)
    return None


def emitted_families():
    """{family_name: set(label_keys)} across the package + bench.py."""
    consts = _collect_constants()
    families = {}

    def labels_of(update_call):
        return {kw.arg for kw in update_call.keywords
                if kw.arg is not None}

    for path in _source_files():
        tree = ast.parse(path.read_text())
        # pass 1 (file-wide): variables and instance attributes bound to
        # a family — `g = reg.gauge(NAME)` and the Telemetry pattern
        # `self._span_hist = registry.histogram(NAME)` used from other
        # methods of the class. Best-effort by identifier name; a
        # collision would at worst union two families' labels, which the
        # mismatch message makes visible.
        var_families = {}
        attr_families = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                name = _family_call_name(node.value, consts)
                if name:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            var_families[tgt.id] = name
                        elif isinstance(tgt, ast.Attribute):
                            attr_families[tgt.attr] = name
        # pass 2: update call sites, chained or through a binding
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _UPDATE_METHODS):
                continue
            target = node.func.value
            name = _family_call_name(target, consts)
            if name is None and isinstance(target, ast.Name):
                name = var_families.get(target.id)
            if name is None and isinstance(target, ast.Attribute):
                name = attr_families.get(target.attr)
            if name is None:
                continue
            families.setdefault(name, set()).update(labels_of(node))
        # declared rows: `(name, help[, value, {label: ...}])`
        for node in ast.walk(tree):
            if isinstance(node, ast.Tuple) and len(node.elts) >= 2:
                name = _resolve_name(node.elts[0], {})
                if name:
                    labels = node.elts[3] if len(node.elts) > 3 else None
                    families.setdefault(name, set()).update(
                        key.value for key in getattr(labels, "keys", [])
                        if isinstance(key, ast.Constant))
        # families created but updated elsewhere (or passed around)
        # still count as emitted by name
        for node in ast.walk(tree):
            name = _family_call_name(node, consts)
            if name:
                families.setdefault(name, set())
    return families


_DOC_ROW_RX = re.compile(r"`(biscotti_[a-z0-9_]+)(\{([^}`]*)\})?`")


def documented_families():
    """{family_name: set(label_keys)} parsed from the OBSERVABILITY.md
    metric table rows (``name{label=,label2=}`` annotations). Multiple
    rows for one family union their labels."""
    families = {}
    for m in _DOC_ROW_RX.finditer(DOC.read_text()):
        name, labels = m.group(1), m.group(3) or ""
        keys = {part.split("=")[0].strip() for part in labels.split(",")
                if "=" in part}
        families.setdefault(name, set()).update(k for k in keys if k)
    return families


def test_every_emitted_family_is_documented():
    emitted = {k: v for k, v in emitted_families().items()
               if k not in SCAN_EXEMPT}
    documented = documented_families()
    missing = sorted(set(emitted) - set(documented))
    assert not missing, (
        "metric families emitted in code but missing from "
        f"docs/OBSERVABILITY.md: {missing} — add a table row per family")


def test_every_documented_family_is_emitted():
    emitted = emitted_families()
    documented = documented_families()
    dead = sorted(set(documented) - set(emitted))
    assert not dead, (
        "metric families documented in docs/OBSERVABILITY.md but emitted "
        f"nowhere in the package: {dead} — delete the stale rows")


def test_documented_label_sets_match_emission():
    emitted = emitted_families()
    documented = documented_families()
    mismatched = []
    for name in sorted(set(emitted) & set(documented)):
        if name in SCAN_EXEMPT:
            continue
        if emitted[name] != documented[name]:
            mismatched.append(
                f"{name}: code={sorted(emitted[name])} "
                f"doc={sorted(documented[name])}")
    assert not mismatched, (
        "label sets disagree between emission sites and the doc table:\n"
        + "\n".join(mismatched))


@pytest.mark.parametrize("fn", [emitted_families, documented_families])
def test_scanner_finds_a_known_family(fn):
    # the scanner itself must not silently go blind: the wire-bytes
    # family exists in both worlds with its three labels
    fams = fn()
    assert "biscotti_wire_bytes_total" in fams
    assert fams["biscotti_wire_bytes_total"] == {"msg_type", "direction",
                                                 "codec"}
