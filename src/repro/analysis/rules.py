"""The rule catalog: repo-specific AST checks over jax/Pallas code.

Every rule is a pure function ``(ModuleInfo) -> list[Finding]`` registered
in ``RULES``. Rules resolve names through the module's import aliases
(``jnp.any`` -> ``jax.numpy.any`` whatever the local alias), so renaming an
import does not dodge a rule. The rule ids are grouped by contract:

  TRC — trace-safety (Python control flow / host syncs on traced values)
  RCP — recompile hazards (per-call jit, array constants baked into jaxprs,
        array-valued static args)
  DET — determinism (unseeded global RNGs, wall-clock time in replayable
        or measured paths)
  DON — buffer-donation discipline (use-after-donate)
  PAL — Pallas kernel contracts (bare int indices, unplanned block sizes,
        non-f32 accumulator scratch)

Heuristics err toward precision: a rule that cries wolf gets baselined into
silence, which is worse than a narrow rule that always means it. The
fixtures in ``tests/fixtures/lint/`` pin each rule's seeded violation AND
its clean twin.
"""
from __future__ import annotations

import ast
import dataclasses
import re

from .findings import Finding

# canonical prefixes after alias resolution
_JNP = "jax.numpy"
_NP = "numpy"
_PL = "jax.experimental.pallas"
_PLTPU = "jax.experimental.pallas.tpu"

# determinism-critical packages: their bitwise-replay guarantees are what
# PR 7's rollback soak and the serve parity tests depend on
REPLAY_SCOPED = ("repro/data/", "repro/serve/", "repro/resilience/")

# module-level references that count as "block sizes are planned" for PAL002
_PLANNING_RE = re.compile(
    r"plan_blocks|check_blocks|autotune_blocks|block_geometry|vmem_bytes"
    r"|resolve_blocks|fits_vmem")
_EXPLICIT_BLOCKS_PRAGMA = "pallas: explicit-blocks"

# numpy.random constructors that are seeded/deterministic by design
_NP_RANDOM_OK = {"default_rng", "Generator", "SeedSequence", "PCG64",
                 "Philox", "MT19937", "SFC64", "BitGenerator"}


# ---------------------------------------------------------------------------
# module model
# ---------------------------------------------------------------------------

class ModuleInfo:
    """Parsed module + alias table + jit-reachability, shared by all rules."""

    def __init__(self, path: str, src: str):
        self.path = path
        self.src = src
        self.lines = src.splitlines()
        self.tree = ast.parse(src)
        self.aliases: dict[str, str] = {}       # local name -> dotted module
        self.from_imports: dict[str, str] = {}  # local name -> qualified name
        self._collect_imports()
        self._parents: dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent
        self.jit_reachable = self._jit_reachable()

    # -- imports ------------------------------------------------------------

    def _collect_imports(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
                    if a.asname:
                        self.aliases[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def qualname(self, node) -> str | None:
        """Resolve a Name/Attribute chain to its canonical dotted path, or
        None if the root is not an imported module / from-import."""
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = node.id
        if root in self.aliases:
            base = self.aliases[root]
        elif root in self.from_imports:
            base = self.from_imports[root]
        elif not parts and root in ("bool", "float", "int"):
            base = root
        else:
            return None
        return ".".join([base] + list(reversed(parts)))

    # -- findings helpers ---------------------------------------------------

    def snippet(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule: str, node: ast.AST, message: str,
                hint: str) -> Finding:
        return Finding(rule=rule, path=self.path, line=node.lineno,
                       col=node.col_offset, message=message, hint=hint,
                       snippet=self.snippet(node.lineno))

    def line_has_pragma(self, lineno: int, pragma: str) -> bool:
        return pragma in self.snippet(lineno)

    # -- jit reachability ---------------------------------------------------

    def _is_jit_entry(self, qn: str | None) -> bool:
        if qn is None:
            return False
        return qn in ("jax.jit", "jax.pjit") or qn.endswith(".pjit") \
            or qn.endswith(".shard_map") or qn.endswith("custom_vjp") \
            or qn.endswith("custom_jvp") or qn == f"{_PL}.pallas_call"

    def _decorator_is_jit(self, dec) -> bool:
        if self._is_jit_entry(self.qualname(dec)):
            return True
        if isinstance(dec, ast.Call):
            qn = self.qualname(dec.func)
            if self._is_jit_entry(qn):
                return True
            # functools.partial(jax.jit, ...) / partial(jax.custom_vjp, ...)
            if qn in ("functools.partial", "partial") and dec.args:
                return self._is_jit_entry(self.qualname(dec.args[0]))
        return False

    def _jit_reachable(self) -> set[ast.FunctionDef]:
        """Functions reachable from a jit/pjit/shard_map/pallas_call entry
        point, via decorators, wrap-calls (``jax.jit(f)``) and same-module
        calls by name (propagated to fixpoint)."""
        defs: dict[str, ast.FunctionDef] = {}
        all_defs: list[ast.FunctionDef] = []
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                all_defs.append(node)
                defs.setdefault(node.name, node)

        seeds: set[ast.FunctionDef] = set()
        for fn in all_defs:
            if any(self._decorator_is_jit(d) for d in fn.decorator_list):
                seeds.add(fn)
        # f passed into jax.jit(f, ...) / pallas_call(f, ...) / partial(...)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            qn = self.qualname(node.func)
            cands = []
            if self._is_jit_entry(qn):
                cands = node.args[:1]
            elif qn in ("functools.partial", "partial") and node.args:
                cands = node.args[:1]  # partial(kernel_fn, ...) fed to pallas
            for a in cands:
                if isinstance(a, ast.Name) and a.id in defs:
                    seeds.add(defs[a.id])

        # propagate through same-module calls by bare name
        reachable = set(seeds)
        changed = True
        while changed:
            changed = False
            for fn in list(reachable):
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call) and \
                            isinstance(node.func, ast.Name):
                        callee = defs.get(node.func.id)
                        if callee is not None and callee not in reachable:
                            reachable.add(callee)
                            changed = True
        return reachable

    def enclosing_function(self, node) -> ast.FunctionDef | None:
        cur = self._parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self._parents.get(cur)
        return None

    def in_jit_reachable(self, node) -> bool:
        fn = self.enclosing_function(node)
        while fn is not None:
            if fn in self.jit_reachable:
                return True
            fn = self.enclosing_function(fn)
        return False


def _is_jnp_call(mi: ModuleInfo, node) -> bool:
    if not isinstance(node, ast.Call):
        return False
    qn = mi.qualname(node.func)
    return qn is not None and qn.startswith(_JNP + ".")


def _contains_jnp_call(mi: ModuleInfo, node) -> ast.Call | None:
    for sub in ast.walk(node):
        if _is_jnp_call(mi, sub):
            return sub
    return None


# ---------------------------------------------------------------------------
# TRC — trace safety
# ---------------------------------------------------------------------------

def rule_trc001(mi: ModuleInfo) -> list[Finding]:
    """Python ``if``/``while`` on a jnp-valued test inside a jit-reachable
    function: under trace the test is a Tracer and raises
    ``TracerBoolConversionError`` (or silently specializes under
    ``static_argnums``)."""
    out = []
    for node in ast.walk(mi.tree):
        if not isinstance(node, (ast.If, ast.While)):
            continue
        if not mi.in_jit_reachable(node):
            continue
        hit = _contains_jnp_call(mi, node.test)
        if hit is not None:
            kind = "if" if isinstance(node, ast.If) else "while"
            out.append(mi.finding(
                "TRC001", node,
                f"Python `{kind}` on a traced value "
                f"(`{ast.unparse(hit)}`) inside a jit-reachable function",
                "branch with jnp.where / jax.lax.cond / jax.lax.select so "
                "the decision stays inside the compiled program"))
    return out


def rule_trc002(mi: ModuleInfo) -> list[Finding]:
    """Host-sync coercions — ``.item()`` / ``bool()`` / ``float()`` /
    ``int()`` over a jnp expression — inside a jit-reachable function."""
    out = []
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        if not mi.in_jit_reachable(node):
            continue
        # x.item()
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
                and not node.args and not node.keywords:
            out.append(mi.finding(
                "TRC002", node,
                "`.item()` inside a jit-reachable function forces a host "
                "sync (and fails under trace)",
                "keep the value on device; reduce with jnp ops and read it "
                "out once, outside the jitted function"))
            continue
        qn = mi.qualname(node.func)
        if qn in ("bool", "float", "int") and len(node.args) == 1 and \
                _contains_jnp_call(mi, node.args[0]):
            out.append(mi.finding(
                "TRC002", node,
                f"`{qn}()` over a traced jnp expression inside a "
                "jit-reachable function",
                "keep the scalar as a jnp value (astype / jnp.where); "
                "coerce to Python only outside the compiled region"))
    return out


def rule_trc003(mi: ModuleInfo) -> list[Finding]:
    """Per-iteration host syncs in loops: ``.item()`` or
    ``jax.device_get`` inside a ``for``/``while`` body serializes the loop
    on device->host readback (the classic hidden hot-loop stall)."""
    out = []
    loops = [n for n in ast.walk(mi.tree)
             if isinstance(n, (ast.For, ast.While))]
    for loop in loops:
        for node in ast.walk(loop):
            if node is loop or not isinstance(node, ast.Call):
                continue
            is_item = isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args
            qn = mi.qualname(node.func)
            is_get = qn == "jax.device_get"
            if not (is_item or is_get):
                continue
            if mi.in_jit_reachable(node):
                continue  # TRC002's jurisdiction
            what = ".item()" if is_item else "jax.device_get"
            out.append(mi.finding(
                "TRC003", node,
                f"`{what}` inside a loop body — a device->host sync every "
                "iteration",
                "accumulate on device and read back once after the loop, "
                "or log every N steps (see train_loop's log_every)"))
    return out


# ---------------------------------------------------------------------------
# RCP — recompile hazards
# ---------------------------------------------------------------------------

def rule_rcp001(mi: ModuleInfo) -> list[Finding]:
    """``jax.jit(...)`` called inside a loop body: every iteration builds a
    fresh jit wrapper with an empty cache — a guaranteed per-iteration
    recompile (the serve budget's nemesis)."""
    out = []
    for loop in ast.walk(mi.tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for node in ast.walk(loop):
            if isinstance(node, ast.Call) and \
                    mi.qualname(node.func) in ("jax.jit", "jax.pjit"):
                out.append(mi.finding(
                    "RCP001", node,
                    "`jax.jit` constructed inside a loop — a fresh compile "
                    "cache (and a recompile) every iteration",
                    "hoist the jit call out of the loop; jit once, call "
                    "many times"))
    return out


def rule_rcp002(mi: ModuleInfo) -> list[Finding]:
    """A jitted inner function closing over an array built in its enclosing
    factory: the array is baked into the jaxpr as a constant, so every
    factory call compiles a distinct executable (step-factory recompile
    hazard) and the constant bypasses donation/sharding."""
    out = []
    for outer in ast.walk(mi.tree):
        if not isinstance(outer, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # arrays assigned in the OUTER body (not inside nested defs)
        arrays: dict[str, ast.AST] = {}
        inner_defs = [n for n in ast.walk(outer)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)) and n is not outer]

        def _in_inner(node):
            return any(node in set(ast.walk(d)) for d in inner_defs)

        for node in ast.walk(outer):
            if isinstance(node, ast.Assign) and not _in_inner(node):
                val = node.value
                if isinstance(val, ast.Call):
                    qn = mi.qualname(val.func)
                    if qn and (qn.startswith(_JNP + ".")
                               or qn.startswith(_NP + ".")
                               or qn.startswith("jax.random.")):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                arrays[t.id] = node
        if not arrays:
            continue
        for inner in inner_defs:
            jitted = any(mi._decorator_is_jit(d) for d in inner.decorator_list)
            if not jitted:
                # `step = jax.jit(inner)` in the same outer body
                for node in ast.walk(outer):
                    if isinstance(node, ast.Call) and \
                            mi._is_jit_entry(mi.qualname(node.func)) and \
                            node.args and isinstance(node.args[0], ast.Name) \
                            and node.args[0].id == inner.name:
                        jitted = True
            if not jitted:
                continue
            local = {a.arg for a in inner.args.args}
            local |= {n.id for n in ast.walk(inner)
                      if isinstance(n, ast.Name)
                      and isinstance(n.ctx, ast.Store)}
            for node in ast.walk(inner):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in arrays and node.id not in local:
                    out.append(mi.finding(
                        "RCP002", node,
                        f"jitted `{inner.name}` closes over array "
                        f"`{node.id}` built in `{outer.name}` — baked in as "
                        "a constant, recompiled per factory call",
                        "pass the array as an argument to the jitted "
                        "function (or thread it through the train state)"))
    return out


def rule_rcp003(mi: ModuleInfo) -> list[Finding]:
    """Array- or container-valued STATIC args: a call site passing a jnp/np
    expression or list/dict/set literal for a parameter declared in
    ``static_argnames`` either fails (unhashable) or keys the jit cache on
    array *identity* — one compile per call."""
    out = []
    # name -> set of static argnames, for `f = jax.jit(g, static_argnames=..)`
    statics: dict[str, set[str]] = {}
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Assign):
            continue
        val = node.value
        if not (isinstance(val, ast.Call)
                and mi.qualname(val.func) in ("jax.jit", "jax.pjit")):
            continue
        names: set[str] = set()
        for kw in val.keywords:
            if kw.arg == "static_argnames":
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        names.add(sub.value)
        if names:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    statics[t.id] = names
    if not statics:
        return out
    for node in ast.walk(mi.tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in statics):
            continue
        for kw in node.keywords:
            if kw.arg not in statics[node.func.id]:
                continue
            bad = None
            if isinstance(kw.value, (ast.List, ast.Dict, ast.Set)):
                bad = "an unhashable container literal"
            elif isinstance(kw.value, ast.Call):
                qn = mi.qualname(kw.value.func)
                if qn and (qn.startswith(_JNP + ".")
                           or qn.startswith(_NP + ".")):
                    bad = "an array expression"
            if bad:
                out.append(mi.finding(
                    "RCP003", kw.value,
                    f"static arg `{kw.arg}` receives {bad} — unhashable or "
                    "identity-keyed, so the jit cache misses every call",
                    "pass a hashable scalar/tuple as the static, or make "
                    "the argument dynamic (drop it from static_argnames)"))
    return out


# ---------------------------------------------------------------------------
# DET — determinism
# ---------------------------------------------------------------------------

def rule_det001(mi: ModuleInfo) -> list[Finding]:
    """The legacy numpy global RNG (``np.random.<fn>``): process-global,
    unseedable per-stream, and invisible to the datapipe checkpoint
    sidecar — it breaks the bitwise batch-replay guarantee."""
    out = []
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        qn = mi.qualname(node.func)
        if not qn or not qn.startswith(_NP + ".random."):
            continue
        fn = qn.rsplit(".", 1)[-1]
        if fn in _NP_RANDOM_OK:
            continue
        out.append(mi.finding(
            "DET001", node,
            f"legacy global numpy RNG `np.random.{fn}` — unseeded, "
            "process-global state outside the datapipe checkpoint",
            "use a held np.random.default_rng(seed) Generator (the repo "
            "convention; see repro.data.loader)"))
    return out


def rule_det002(mi: ModuleInfo) -> list[Finding]:
    """The Python stdlib ``random`` module's global functions — same
    process-global nondeterminism as DET001, same fix."""
    out = []
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        qn = mi.qualname(node.func)
        if not qn or not qn.startswith("random."):
            continue
        fn = qn.split(".", 1)[1]
        if fn.split(".")[0] in ("Random", "SystemRandom"):
            continue  # an instance is held + seeded explicitly (or crypto)
        out.append(mi.finding(
            "DET002", node,
            f"stdlib global RNG `random.{fn}` — unseeded process-global "
            "state",
            "hold a random.Random(seed) instance, or use "
            "np.random.default_rng(seed)"))
    return out


def rule_det003(mi: ModuleInfo) -> list[Finding]:
    """``time.time()`` — non-monotonic (NTP steps it) so durations computed
    from it are wrong, and as a *value* in the replay-scoped packages it is
    nondeterministic input."""
    out = []
    scoped = any(s in mi.path for s in REPLAY_SCOPED)
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        if mi.qualname(node.func) not in ("time.time", "time.time_ns"):
            continue
        where = "a bitwise-replay-scoped module" if scoped else \
            "a measured/timed path"
        out.append(mi.finding(
            "DET003", node,
            f"`time.time()` in {where} — non-monotonic wall clock",
            "time durations with time.perf_counter(); drive deadlines with "
            "time.monotonic(); replay-scoped code must not read clocks"))
    return out


# ---------------------------------------------------------------------------
# DON — donation discipline
# ---------------------------------------------------------------------------

def _donated_indices(call: ast.Call) -> tuple[int, ...]:
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            vals = []
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, int):
                    vals.append(sub.value)
            return tuple(vals) or (0,)
    return ()


def rule_don001(mi: ModuleInfo) -> list[Finding]:
    """Use-after-donate: a buffer passed at a donated position of a jitted
    step is CONSUMED — XLA may alias its memory for the outputs, and
    reading it afterwards returns garbage (or errors on TPU)."""
    out = []

    def _enclosing_stmt(node):
        cur = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = mi._parents.get(cur)
        return cur

    for fn in ast.walk(mi.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # donating callables assigned in this function body
        donating: dict[str, tuple[int, ...]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    mi.qualname(node.value.func) in ("jax.jit", "jax.pjit"):
                idx = _donated_indices(node.value)
                if idx:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            donating[t.id] = idx
        if not donating:
            continue
        # source-position-ordered event scan. Within one line, loads run
        # before stores before donations — so the canonical safe pattern
        # `state, out = step(state, batch)` (donate + rebind in one
        # statement) never taints `state`: the donation event checks its
        # enclosing statement for a rebind and skips tainting.
        events = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Name):
                kind = 0 if isinstance(node.ctx, ast.Load) else 1
                events.append((node.lineno, kind, node.col_offset, node))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id in donating:
                events.append((node.lineno, 2, node.col_offset, node))
        donated: dict[str, int] = {}  # name -> donation lineno
        for lineno, kind, _col, node in sorted(events, key=lambda e: e[:3]):
            if kind == 0 and node.id in donated:
                out.append(mi.finding(
                    "DON001", node,
                    f"`{node.id}` read after being donated on line "
                    f"{donated[node.id]} — its buffer may already be "
                    "aliased by the step's outputs",
                    "rebind the result (`state = step(state, ...)`) and "
                    "only use the returned value, or compile with "
                    "donate=False for debugging"))
                del donated[node.id]
            elif kind == 1 and node.id in donated:
                del donated[node.id]
            elif kind == 2:
                stmt = _enclosing_stmt(node)
                for i in donating[node.func.id]:
                    if i < len(node.args) and \
                            isinstance(node.args[i], ast.Name):
                        name = node.args[i].id
                        rebinds = stmt is not None and any(
                            isinstance(n, ast.Name) and n.id == name and
                            isinstance(n.ctx, ast.Store)
                            for n in ast.walk(stmt))
                        if not rebinds:
                            donated[name] = node.lineno
    return out


# ---------------------------------------------------------------------------
# PAL — Pallas contracts
# ---------------------------------------------------------------------------

def rule_pal002(mi: ModuleInfo) -> list[Finding]:
    """Every ``pallas_call`` site must route its block sizes through a
    budget/planning helper (``egnn_edge.budget``-style) or carry an explicit
    ``# pallas: explicit-blocks`` override — unplanned tile sizes compile
    fine under the CPU interpreter and OOM VMEM on the first TPU run."""
    calls = [n for n in ast.walk(mi.tree)
             if isinstance(n, ast.Call)
             and mi.qualname(n.func) == f"{_PL}.pallas_call"]
    if not calls:
        return []
    if _PLANNING_RE.search(mi.src):
        return []
    out = []
    for node in calls:
        if mi.line_has_pragma(node.lineno, _EXPLICIT_BLOCKS_PRAGMA):
            continue
        out.append(mi.finding(
            "PAL002", node,
            "pallas_call with no block planning in the module — tile sizes "
            "never validated against a VMEM budget",
            "derive blocks via a plan/check helper (see "
            "repro.kernels.egnn_edge.budget) or annotate the call with "
            f"`# {_EXPLICIT_BLOCKS_PRAGMA}(<why the tiles are safe>)`"))
    return out


def rule_pal003(mi: ModuleInfo) -> list[Finding]:
    """Scratch accumulators must be f32: a bf16/f16 VMEM scratch used for
    cross-block reduction loses ~3 decimal digits per 1k accumulated terms
    (paper-shape E=768 edge blocks make that visible in gradients)."""
    out = []
    low = {f"{_JNP}.bfloat16", f"{_JNP}.float16"}
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        if mi.qualname(node.func) != f"{_PL}.pallas_call":
            continue
        for kw in node.keywords:
            if kw.arg != "scratch_shapes":
                continue
            for sub in ast.walk(kw.value):
                if not (isinstance(sub, ast.Call)
                        and (mi.qualname(sub.func) or "").endswith(".VMEM")):
                    continue
                dtype_nodes = list(sub.args[1:2]) + \
                    [k.value for k in sub.keywords if k.arg == "dtype"]
                for dn in dtype_nodes:
                    if mi.qualname(dn) in low:
                        out.append(mi.finding(
                            "PAL003", dn,
                            f"VMEM scratch with dtype "
                            f"`{ast.unparse(dn)}` — reductions need an f32 "
                            "accumulator",
                            "accumulate in jnp.float32 scratch and cast on "
                            "the final flush (o_ref.dtype), as "
                            "segment_sum/_ss_kernel does"))
    return out


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    doc: str
    fn: object

    def run(self, mi: ModuleInfo) -> list[Finding]:
        return self.fn(mi)


def _mk(id, name, fn):
    return Rule(id=id, name=name, doc=(fn.__doc__ or "").strip(), fn=fn)


RULES: list[Rule] = [
    _mk("TRC001", "trace-host-branch", rule_trc001),
    _mk("TRC002", "trace-host-sync", rule_trc002),
    _mk("TRC003", "hotloop-host-sync", rule_trc003),
    _mk("RCP001", "recompile-jit-in-loop", rule_rcp001),
    _mk("RCP002", "recompile-closure-array", rule_rcp002),
    _mk("RCP003", "recompile-array-static", rule_rcp003),
    _mk("DET001", "det-np-global-rng", rule_det001),
    _mk("DET002", "det-py-random", rule_det002),
    _mk("DET003", "det-wallclock", rule_det003),
    _mk("DON001", "donate-use-after", rule_don001),
    _mk("PAL002", "pallas-unplanned-blocks", rule_pal002),
    _mk("PAL003", "pallas-scratch-dtype", rule_pal003),
]


def rule_ids() -> list[str]:
    return [r.id for r in RULES]


_ALLOW_RE = re.compile(r"lint:\s*allow\(([A-Z0-9_,\s]+)\)")


def _inline_allowed(mi: ModuleInfo, f: Finding) -> bool:
    """``# lint: allow(RULEID): reason`` on the flagged line (or the line
    above) suppresses that rule there — for deliberate exceptions a
    baseline entry would misrepresent (e.g. one-jit-per-swept-config
    benchmark loops). DET*/PAL* findings cannot be inline-allowed: those
    must be fixed (same policy as ``baseline.NEVER_BASELINE``)."""
    if f.rule.startswith(("DET", "PAL")):
        return False
    for ln in (f.line, f.line - 1):
        m = _ALLOW_RE.search(mi.snippet(ln))
        if m and f.rule in {x.strip() for x in m.group(1).split(",")}:
            return True
    return False


def run_rules(path: str, src: str, *, rules=None) -> list[Finding]:
    """All findings for one module, deduplicated (nested AST walks can
    visit a node once per enclosing scope) and filtered through inline
    ``lint: allow(...)`` pragmas. ``rules``: optional filter by rule id or
    name."""
    mi = ModuleInfo(path, src)
    wanted = set(rules) if rules else None
    out: list[Finding] = []
    seen: set[tuple] = set()
    for rule in RULES:
        if wanted is not None and rule.id not in wanted \
                and rule.name not in wanted:
            continue
        for f in rule.run(mi):
            key = (f.rule, f.line, f.col)
            if key not in seen and not _inline_allowed(mi, f):
                seen.add(key)
                out.append(f)
    return out
