"""Sharding-conformance rules: the partition-rule layer becomes checkable.

ROADMAP item 1 (the unified partition-rule layer) is the most invasive
refactor on the books, and until now nothing understood *sharding*: a dead
or shadowed entry in ``parallel/sharding.py::LLAMA_RULES``, a
``PartitionSpec`` naming an axis no mesh defines, or a spec whose mesh-axis
product stops dividing a leaf dim all compile fine and surface — if ever —
as a deep XLA partitioner error or a silent full-replication bandwidth tax.
This module closes that gap in two tiers:

**Fast (pure-AST, ride the default <10s lint stage):**

* ``shard-undefined-axis`` — string axis names inside
  ``PartitionSpec``/``P``/``NamedSharding`` literals (including specs built
  for ``with_sharding_constraint``) must be axes some mesh builder defines:
  the ``AxisNames`` table in ``parallel/mesh.py``, or a module-local
  ``Mesh(..., ("x",))`` construction (diagnostics meshes).  A typo'd axis
  raises at run time only on the code path that hits it; here it's red on
  every lint.
* ``shard-unsharded-device-put`` — a bare single-argument
  ``jax.device_put(x)`` on a multi-chip path (``parallel``/``train``/
  ``serve``/``transport``/``data`` subpackages) lands the array wherever
  the default device points — usually device 0 or full replication — and
  GSPMD quietly reshards it at the next jit boundary.  Pass the rule-table
  ``NamedSharding`` explicitly.

**Heavy (import jax / compile; excluded from the default registry, run by
the ``shard-audit-fast`` ci_check stage via ``--rules``):**

* ``shard-rule-coverage`` — reconstructs every ``PartitionRules`` table
  from its source AST (so mutation tests can rewrite the table text) and
  validates it against abstract ``jax.eval_shape`` param trees of the
  catalog presets (dense+LoRA, QLoRA int4, MoE, multimodal): every leaf
  matched by a rule; rules that match nothing (dead) or whose every match
  is taken by an earlier pattern (shadowed) flagged at their own line; spec
  axis names checked against ``AxisNames``; and — the deleted-rule trap —
  any matmul-weight leaf (``kernel``/``embedding``/``experts_*``/
  ``lora_*``) falling through to the bare ``.*`` catch-all is red, because
  replicate-by-default for a weight family is never a decision someone
  made on purpose.
* ``shard-divisibility`` — for each catalog topology (``train/aot.py::
  REALSCALE`` real-shape configs plus the simulated audit meshes), proves
  the resolved spec of every leaf names real mesh axes and that the
  mesh-axis product divides the leaf dim it shards — the static twin of
  the runtime check ``parallel/sharding.py::validate_spec`` now performs.
* ``collective-conformance`` — runs the AOT collective audit
  (``analysis/collective_audit.py``) and diffs the compiled HLO's
  collective set BOTH WAYS against the machine-checked **Collective
  catalog** in ``docs/performance.md``: an undocumented collective (the
  headline bug class: an unexpected full-param all-gather in the step
  body) or a documented-but-vanished one is red.

Fixture opt-outs mirror lint v2: no ``parallel/mesh.py`` module → axis
rules skip; no ``PartitionRules`` table → coverage skips; no Collective
catalog heading → conformance skips.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Any, Iterator

from ._astutil import dotted_name, terminal_name
from .engine import register_project

# ---------------------------------------------------------------------------
# mesh-axis extraction (shared)
# ---------------------------------------------------------------------------


def _mesh_module(project):
    for module in project.modules.values():
        if Path(module.path).as_posix().endswith("parallel/mesh.py"):
            return module
    return None


def _resolve_axis_value(node: ast.AST, attr_map: dict[str, Any]):
    """Evaluate an ``AxisNames`` class-body value: a string constant, a
    reference to an earlier attr, or a tuple of either (``BATCH_AXES =
    (DATA, FSDP)``)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name) and node.id in attr_map:
        return attr_map[node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        parts = [_resolve_axis_value(e, attr_map) for e in node.elts]
        if all(p is not None for p in parts):
            return tuple(parts)
    return None


def _axis_table(mesh_module) -> tuple[dict[str, Any], set[str]] | None:
    """``(AxisNames attr -> value, set of defined axis name strings)`` from
    the mesh module's AST, or None when it defines no ``AxisNames``."""
    cls = next(
        (n for n in ast.walk(mesh_module.tree)
         if isinstance(n, ast.ClassDef) and n.name == "AxisNames"),
        None,
    )
    if cls is None:
        return None
    attr_map: dict[str, Any] = {}
    values: set[str] = set()
    for stmt in cls.body:
        if isinstance(stmt, ast.Assign):
            targets = [t.id for t in stmt.targets if isinstance(t, ast.Name)]
            value = stmt.value
        elif isinstance(stmt, ast.AnnAssign) and \
                isinstance(stmt.target, ast.Name) and stmt.value is not None:
            targets, value = [stmt.target.id], stmt.value
        else:
            continue
        resolved = _resolve_axis_value(value, attr_map)
        if resolved is None:
            continue
        for t in targets:
            attr_map[t] = resolved
        for v in (resolved if isinstance(resolved, tuple) else (resolved,)):
            if isinstance(v, str):
                values.add(v)
    return attr_map, values


def _call_target(module, call: ast.Call) -> str:
    """Best-effort absolute dotted target of a call's callee."""
    func = call.func
    if isinstance(func, ast.Name):
        return module.imports.get(func.id, func.id)
    dotted = dotted_name(func)
    if dotted:
        head, _, rest = dotted.partition(".")
        head = module.imports.get(head, head)
        return f"{head}.{rest}" if rest else head
    return terminal_name(func) or ""


def _local_mesh_axes(module) -> set[str]:
    """Axis names a module defines by constructing ``Mesh(...)`` directly
    (diagnostics meshes like ``Mesh(devs, ("x",))``) — legal in specs within
    that module even though no shared builder exports them."""
    out: set[str] = set()
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if _call_target(module, node).split(".")[-1] != "Mesh":
            continue
        sources = list(node.args[1:]) + [
            kw.value for kw in node.keywords if kw.arg == "axis_names"
        ]
        for src in sources:
            for c in ast.walk(src):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    out.add(c.value)
    return out


# ---------------------------------------------------------------------------
# fast rule: undefined axis names in sharding literals
# ---------------------------------------------------------------------------

_SPEC_CTORS = ("PartitionSpec", "NamedSharding")


def _axis_constants(call: ast.Call) -> Iterator[ast.Constant]:
    """String constants in a spec constructor's POSITIONAL args (keyword
    args like ``memory_kind="pinned_host"`` are not axis names), skipping
    nested calls — the outer walk visits those on its own."""
    for arg in call.args:
        stack: list[ast.AST] = [arg]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Call):
                continue
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                yield n
            else:
                stack.extend(ast.iter_child_nodes(n))


@register_project(
    "shard-undefined-axis",
    "sharding",
    "PartitionSpec/NamedSharding literals may only name axes a mesh defines",
)
def shard_undefined_axis(project):
    mesh_mod = _mesh_module(project)
    table = _axis_table(mesh_mod) if mesh_mod is not None else None
    if table is None:
        return  # fixture trees without a mesh module opt out
    _attr_map, defined = table
    for module in project.modules.values():
        # cheap source pre-filter: most modules never spell a spec ctor,
        # and this rule rides the 10s default lint stage
        if not any(ctor in module.src for ctor in _SPEC_CTORS):
            continue
        local: set[str] | None = None  # lazy: one extra AST walk, and only
        for node in ast.walk(module.tree):  # for modules with unknown axes
            if not isinstance(node, ast.Call):
                continue
            if _call_target(module, node).split(".")[-1] not in _SPEC_CTORS:
                continue
            for const in _axis_constants(node):
                if const.value in defined:
                    continue
                if local is None:
                    local = _local_mesh_axes(module)
                if const.value not in local:
                    yield (
                        module.path, const.lineno, const.col_offset,
                        f"sharding literal names axis {const.value!r}, but "
                        "no mesh defines it (parallel/mesh.py AxisNames: "
                        f"{', '.join(sorted(defined))}) — a typo'd axis "
                        "raises only on the code path that hits it",
                    )


# ---------------------------------------------------------------------------
# fast rule: device_put without explicit placement on multi-chip paths
# ---------------------------------------------------------------------------

_MULTICHIP_SEGMENTS = {"parallel", "train", "serve", "transport", "data"}


@register_project(
    "shard-unsharded-device-put",
    "sharding",
    "jax.device_put on multi-chip paths must pass an explicit sharding",
)
def shard_unsharded_device_put(project):
    if _mesh_module(project) is None:
        return  # single-chip fixture trees opt out
    for module in project.modules.values():
        if not (_MULTICHIP_SEGMENTS & set(module.name.split("."))):
            continue
        if "device_put" not in module.src:  # skip the AST walk entirely
            continue
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_target(module, node) != "jax.device_put":
                continue
            explicit = len(node.args) >= 2 or any(
                kw.arg == "device" for kw in node.keywords
            )
            if not explicit:
                yield (
                    module.path, node.lineno, node.col_offset,
                    "jax.device_put without an explicit sharding on a "
                    "multi-chip path lands the array on the default device "
                    "(replicated or device 0) and GSPMD silently reshards "
                    "it at the next jit boundary — pass the rule-table "
                    "NamedSharding (parallel/sharding.py)",
                )


# ---------------------------------------------------------------------------
# PartitionRules table extraction (AST — mutation tests rewrite the source)
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("line", "col", "pattern", "spec")

    def __init__(self, line, col, pattern, spec):
        self.line, self.col = line, col
        self.pattern = pattern  # str | None (unparseable)
        self.spec = spec  # tuple of (None | str | tuple[str, ...]) | None


class _Table:
    __slots__ = ("module", "name", "line", "entries")

    def __init__(self, module, name, line, entries):
        self.module, self.name, self.line = module, name, line
        self.entries = entries

    @property
    def parsed(self) -> bool:
        return all(
            e.pattern is not None and e.spec is not None for e in self.entries
        )


def _eval_spec_entry(node: ast.AST, attr_map: dict[str, Any]):
    """One positional arg of a ``P(...)`` spec: None, an axis string, an
    ``Ax.NAME`` attribute, or a tuple of those.  Returns the Python value
    or raises ValueError when unresolvable."""
    if isinstance(node, ast.Constant) and (
        node.value is None or isinstance(node.value, str)
    ):
        return node.value
    if isinstance(node, ast.Attribute) and node.attr in attr_map:
        return attr_map[node.attr]
    if isinstance(node, ast.Name) and node.id in attr_map:
        return attr_map[node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        parts = []
        for e in node.elts:
            v = _eval_spec_entry(e, attr_map)
            parts.extend(v) if isinstance(v, tuple) else parts.append(v)
        return tuple(parts)
    raise ValueError(ast.dump(node))


def _eval_spec(node: ast.AST, attr_map: dict[str, Any], module):
    """A rule entry's spec: a ``P(...)``/``PartitionSpec(...)`` call whose
    args all evaluate; None when it doesn't."""
    if not isinstance(node, ast.Call) or node.keywords:
        return None
    if _call_target(module, node).split(".")[-1] != "PartitionSpec":
        return None
    try:
        return tuple(_eval_spec_entry(a, attr_map) for a in node.args)
    except ValueError:
        return None


def _find_tables(project, attr_map: dict[str, Any]) -> list[_Table]:
    tables: list[_Table] = []
    for module in project.modules.values():
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            if _call_target(module, call).split(".")[-1] != "PartitionRules":
                continue
            if not (call.args and isinstance(call.args[0],
                                             (ast.List, ast.Tuple))):
                continue
            name = next(
                (t.id for t in node.targets if isinstance(t, ast.Name)),
                "<anon>",
            )
            entries = []
            for elt in call.args[0].elts:
                if isinstance(elt, ast.Tuple) and len(elt.elts) == 2:
                    pat_node, spec_node = elt.elts
                    pattern = (
                        pat_node.value
                        if isinstance(pat_node, ast.Constant)
                        and isinstance(pat_node.value, str) else None
                    )
                    spec = _eval_spec(spec_node, attr_map, module)
                else:
                    pattern = spec = None
                entries.append(
                    _Entry(elt.lineno, elt.col_offset, pattern, spec)
                )
            tables.append(_Table(module, name, node.lineno, entries))
    return tables


def _build_rules(table: _Table):
    """Runtime ``PartitionRules`` reconstructed from the parsed AST table —
    first-match semantics, pipe-axis stacking and rank handling all come
    from the real class, not a reimplementation."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import PartitionRules

    return PartitionRules(
        [(e.pattern, P(*e.spec)) for e in table.entries]
    )


# ---------------------------------------------------------------------------
# abstract catalog param trees (heavy, cached per process)
# ---------------------------------------------------------------------------

_VARIANT_CACHE: dict[str, list[tuple[str, Any]]] | None = None
_PRESET_CACHE: dict[str, list[tuple[str, Any]]] = {}


def _shape_leaves(model, *args) -> list[tuple[str, Any]]:
    import jax

    from ..parallel.sharding import key_path_str

    shapes = jax.eval_shape(
        model.init, {"params": jax.random.PRNGKey(0)}, *args
    )
    return [
        (key_path_str(kp), leaf)
        for kp, leaf in jax.tree_util.tree_leaves_with_path(shapes)
    ]


def _validation_trees() -> dict[str, list[tuple[str, Any]]]:
    """Abstract param trees spanning every weight family the rule tables
    must cover: dense+LoRA (untied, so lm_head exists), QLoRA int4 scales,
    MoE experts + router, latent attention with a selection-biased router,
    a state-space mixer beside attention, a pattern of single-mixer layers
    with experts in a latent, a pattern of attention kinds (a window layer's
    sink), and the multimodal projector + ViT tower.  All
    ``eval_shape`` — no parameter memory is allocated."""
    global _VARIANT_CACHE
    if _VARIANT_CACHE is not None:
        return _VARIANT_CACHE
    import jax.numpy as jnp

    from ..models.llama import PRESETS, LlamaForCausalLM
    from ..models.lora import (GATED_MIXER_TARGETS, HYBRID_TARGETS, MLA_TARGETS,
                               PATTERN_TARGETS, LoRAConfig)
    from ..models.multimodal import MM_PRESETS, LlavaForCausalLM

    tokens = jnp.zeros((1, 8), jnp.int32)
    out: dict[str, list[tuple[str, Any]]] = {}
    cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    out["tiny-test+lora"] = _shape_leaves(LlamaForCausalLM(cfg), tokens)
    cfg_q = PRESETS["tiny-test"].replace(
        lora=LoRAConfig(rank=4), quantize_base=True
    )
    out["tiny-test+qlora"] = _shape_leaves(LlamaForCausalLM(cfg_q), tokens)
    cfg_moe = PRESETS["tiny-moe-test"].replace(
        lora=LoRAConfig(rank=4), quantize_base=True
    )
    out["tiny-moe-test+qlora"] = _shape_leaves(
        LlamaForCausalLM(cfg_moe), tokens
    )
    cfg_mla = PRESETS["tiny-mla-moe-test"].replace(
        lora=LoRAConfig(rank=4, targets=MLA_TARGETS)
    )
    out["tiny-mla-moe-test+lora"] = _shape_leaves(
        LlamaForCausalLM(cfg_mla), tokens
    )
    cfg_ssm = PRESETS["tiny-falcon-h1-test"].replace(
        lora=LoRAConfig(rank=4, targets=HYBRID_TARGETS)
    )
    out["tiny-falcon-h1-test+lora"] = _shape_leaves(
        LlamaForCausalLM(cfg_ssm), tokens
    )
    cfg_pattern = PRESETS["tiny-nemotron-h-test"].replace(
        lora=LoRAConfig(rank=4, targets=PATTERN_TARGETS)
    )
    out["tiny-nemotron-h-test+lora"] = _shape_leaves(
        LlamaForCausalLM(cfg_pattern), tokens
    )
    # whole blocks by their attention's kind: unlike k/v shapes, a sink leaf
    cfg_kinds = PRESETS["tiny-mimo-v2-test"].replace(lora=LoRAConfig(rank=4))
    out["tiny-mimo-v2-test+lora"] = _shape_leaves(
        LlamaForCausalLM(cfg_kinds), tokens
    )
    # whole blocks by their mixer's kind: an output gate beside q/k/v/o in
    # both, q/k (and output) norm scales, every lightning head its own keys
    cfg_mixers = PRESETS["tiny-minicpm-sala-test"].replace(
        lora=LoRAConfig(rank=4, targets=GATED_MIXER_TARGETS)
    )
    out["tiny-minicpm-sala-test+lora"] = _shape_leaves(
        LlamaForCausalLM(cfg_mixers), tokens
    )
    mm = MM_PRESETS["tiny-mm-test"].replace(lora=LoRAConfig(rank=4))
    pixels = jnp.zeros(
        (1, mm.vision.image_size, mm.vision.image_size, 3), jnp.float32
    )
    out["tiny-mm-test+lora"] = _shape_leaves(
        LlavaForCausalLM(mm), tokens, pixels
    )
    _VARIANT_CACHE = out
    return out


def _preset_leaves(preset: str) -> list[tuple[str, Any]]:
    """Abstract param tree of a REALSCALE preset with the aot.py LoRA
    setup (rank 16) — real shapes, zero bytes allocated."""
    if preset not in _PRESET_CACHE:
        import jax.numpy as jnp

        from ..models.llama import PRESETS, LlamaForCausalLM
        from ..models.lora import LoRAConfig

        cfg = PRESETS[preset].replace(lora=LoRAConfig(rank=16))
        _PRESET_CACHE[preset] = _shape_leaves(
            LlamaForCausalLM(cfg), jnp.zeros((1, 8), jnp.int32)
        )
    return _PRESET_CACHE[preset]


# ---------------------------------------------------------------------------
# heavy rule: rule-table coverage (dead / shadowed / unmatched / fallthrough)
# ---------------------------------------------------------------------------


def _weight_like(path: str) -> bool:
    """Matmul-weight leaves: the ones whose sharding is always a decision.
    Norm scales / biases / rotary tables replicate by design and may ride
    the catch-all."""
    last = path.rsplit("/", 1)[-1]
    return (
        last in ("kernel", "embedding")
        or last.startswith("experts_")
        or last.startswith("lora_")
    )


@register_project(
    "shard-rule-coverage",
    "sharding",
    "every PartitionRules entry is live and every catalog param leaf is covered",
    heavy=True,
)
def shard_rule_coverage(project):
    mesh_mod = _mesh_module(project)
    table_info = _axis_table(mesh_mod) if mesh_mod is not None else None
    if table_info is None:
        return
    attr_map, defined = table_info
    tables = [t for t in _find_tables(project, attr_map) if t.parsed]
    if not tables:
        return
    trees = _validation_trees()

    for table in tables:
        # spec axis names against the mesh builders
        for entry in table.entries:
            for part in entry.spec:
                axes = part if isinstance(part, tuple) else (part,)
                for ax in axes:
                    if ax is not None and ax not in defined:
                        yield (
                            table.module.path, entry.line, entry.col,
                            f"rule {entry.pattern!r} spec names axis "
                            f"{ax!r}, but no mesh defines it (AxisNames: "
                            f"{', '.join(sorted(defined))})",
                        )

        rules = _build_rules(table)
        n = len(table.entries)
        catch_all = (
            n - 1 if table.entries and table.entries[-1].pattern == ".*"
            else None
        )
        first_hits: dict[int, str] = {}  # rule index -> witness path
        all_paths: list[str] = []
        for variant, leaves in trees.items():
            for path, _leaf in leaves:
                all_paths.append(path)
                idx = rules.match_index(path)
                if idx is None:
                    yield (
                        table.module.path, table.line, 0,
                        f"param leaf {path!r} ({variant}) is matched by no "
                        f"rule in {table.name} — every leaf needs an "
                        "explicit sharding decision (or a catch-all)",
                    )
                    continue
                first_hits.setdefault(idx, path)
                if idx == catch_all and _weight_like(path):
                    yield (
                        table.module.path, table.entries[idx].line,
                        table.entries[idx].col,
                        f"weight leaf {path!r} ({variant}) falls through to "
                        f"the bare catch-all in {table.name} — a "
                        "kernel/embedding replicated by DEFAULT is a "
                        "deleted or never-written rule, not a decision; "
                        "add an explicit entry for this family",
                    )

        compiled = [re.compile(e.pattern) for e in table.entries]
        for i, entry in enumerate(table.entries):
            if i in first_hits:
                continue
            witness = next(
                (p for p in all_paths if compiled[i].search(p)), None
            )
            if witness is None:
                yield (
                    table.module.path, entry.line, entry.col,
                    f"dead rule: {entry.pattern!r} matches no param leaf of "
                    "any catalog preset (dense+LoRA, QLoRA, MoE, "
                    "multimodal) — delete it, or it is a typo'd pattern "
                    "silently replicating the leaves it meant to shard",
                )
            else:
                j = rules.match_index(witness)
                shadow = table.entries[j]
                yield (
                    table.module.path, entry.line, entry.col,
                    f"shadowed rule: every leaf {entry.pattern!r} matches "
                    f"(e.g. {witness!r}) is taken first by the earlier rule "
                    f"{shadow.pattern!r} (line {shadow.line}) — reorder or "
                    "delete; first match wins",
                )


# ---------------------------------------------------------------------------
# heavy rule: axis sizes divide leaf dims on every catalog topology
# ---------------------------------------------------------------------------


def _catalog_topologies() -> list[tuple[str, str, dict[str, int]]]:
    """``(config name, preset, resolved axis sizes)`` for every catalog
    topology: the REALSCALE real-shape configs plus the simulated
    collective-audit meshes (tiny preset)."""
    from ..parallel.mesh import MeshSpec
    from ..train.aot import REALSCALE
    from .collective_audit import TOPOLOGIES

    out = []
    for name, spec in REALSCALE.items():
        sizes = MeshSpec(**spec["mesh"]).resolve(spec["n_devices"])
        out.append((name, spec["preset"], sizes))
    for name, spec in TOPOLOGIES.items():
        sizes = MeshSpec(**spec["mesh"]).resolve(spec["n_devices"])
        out.append((name, "tiny-test", sizes))
    return out


def _divisibility_error(
    path: str, shape: tuple, spec, sizes: dict[str, int]
) -> str | None:
    for dim, part in enumerate(spec):
        if part is None:
            continue
        axes = part if isinstance(part, (tuple, list)) else (part,)
        factor = 1
        for ax in axes:
            if ax not in sizes:
                return (
                    f"resolves {path!r} to spec {tuple(spec)} naming mesh "
                    f"axis {ax!r}, which this topology does not define"
                )
            factor *= sizes[ax]
        if dim >= len(shape) or (factor > 1 and shape[dim] % factor):
            size = shape[dim] if dim < len(shape) else "<missing>"
            return (
                f"resolves {path!r} (shape {tuple(shape)}) to spec "
                f"{tuple(spec)}, but dim {dim} (size {size}) is not "
                f"divisible by the {factor}-way sharding over {tuple(axes)}"
            )
    return None


@register_project(
    "shard-divisibility",
    "sharding",
    "resolved specs divide real leaf dims on every catalog topology",
    heavy=True,
)
def shard_divisibility(project):
    mesh_mod = _mesh_module(project)
    table_info = _axis_table(mesh_mod) if mesh_mod is not None else None
    if table_info is None:
        return
    attr_map, _defined = table_info
    tables = [t for t in _find_tables(project, attr_map) if t.parsed]
    if not tables:
        return

    for table in tables:
        rules = _build_rules(table)
        seen: set[tuple[int, str]] = set()  # (rule idx, message) dedup
        for cfg_name, preset, sizes in _catalog_topologies():
            for path, leaf in _preset_leaves(preset):
                idx = rules.match_index(path)
                if idx is None:
                    continue  # shard-rule-coverage owns unmatched leaves
                spec = rules.spec_for(path, leaf)
                err = _divisibility_error(path, tuple(leaf.shape), spec, sizes)
                if err is None:
                    continue
                key = (idx, err)
                if key in seen:
                    continue
                seen.add(key)
                entry = table.entries[idx]
                yield (
                    table.module.path, entry.line, entry.col,
                    f"on topology {cfg_name} ({_fmt_sizes(sizes)}), rule "
                    f"{entry.pattern!r} {err} — this compiles into a deep "
                    "XLA partitioner error (or worse, silent padding)",
                )


def _fmt_sizes(sizes: dict[str, int]) -> str:
    return "×".join(f"{k}{v}" for k, v in sizes.items() if v > 1) or "1 chip"


# ---------------------------------------------------------------------------
# heavy rule: compiled collectives match docs/performance.md
# ---------------------------------------------------------------------------


@register_project(
    "collective-conformance",
    "sharding",
    "compiled HLO collective sets match the Collective catalog in docs/performance.md",
    heavy=True,
)
def collective_conformance(project):
    docs = project.docs_file("performance.md")
    if docs is None:
        return  # fixture trees without docs opt out
    from .collective_audit import diff_catalog, full_audit, parse_catalog

    catalog, heading_line = parse_catalog(
        docs.read_text(encoding="utf-8")
    )
    if not catalog:
        return  # no catalog section yet: nothing to conform to
    observed = full_audit()
    for msg in diff_catalog(observed, catalog):
        yield (str(docs), heading_line, 0, msg)
