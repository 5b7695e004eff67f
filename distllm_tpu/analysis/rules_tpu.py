"""TPU-serving rules: the hazard classes that cost serving throughput.

Four rules, all grounded in measured failure modes of this codebase:

- ``host-sync-in-hot-path`` — a blocking device→host transfer inside the
  engine window loop or a model dispatch path serializes the pipelined
  decode stream; this is exactly the host-gap / 830-vs-1101 tok/s class
  of regression the attribution layer (PR 7/8) measures *after the
  fact*. The analyzer bans the spellings up front; the designed fetch
  points carry justified suppressions.
- ``traced-python-branch`` — an ``if``/``while``/``assert`` on a traced
  array value inside jit/pallas-reachable code either raises a
  ConcretizationTypeError or, worse, silently bakes one trace-time
  branch into the compiled executable (the silent-recompile / tracer-
  leak bug class).
- ``lock-discipline`` — attributes annotated ``# guarded by self._lock``
  may only be touched inside a matching ``with`` block: a static race
  detector for the shared state the engine thread, the aiohttp event
  loop, and watchdog threads all touch.
- ``nondeterminism-in-dispatch`` — ``time.*``/``random.*`` calls inside
  traced functions execute ONCE at trace time and bake a constant into
  the executable: the code reads as dynamic but is frozen, and
  recompiles silently resample it.

Traced-function discovery is shared: a function is traced when it is
decorated with / wrapped by ``jax.jit`` (including ``functools.partial``
forms), passed to ``pallas_call``, marked ``# distlint: traced`` on its
``def`` line, or referenced by name from an already-traced function in
the same module (a same-module transitive closure — ``lax.scan`` bodies
and helper layers are reached without a call-graph database).
"""

from __future__ import annotations

import ast

from distllm_tpu.analysis.core import (
    Diagnostic,
    Project,
    Rule,
    SourceFile,
    register,
)

# Attribute reads that never concretize a traced array: branching on
# these stays host-side/static and must not trip traced-python-branch.
_STATIC_ATTRS = frozenset(
    {'shape', 'dtype', 'ndim', 'size', 'sharding', 'format'}
)

# Call roots whose results are device values (for host-sync tracking)
# when dotted from jnp/jax, e.g. jnp.zeros(...), jax.random.split(...).
_DEVICE_MODULES = ('jnp', 'jax', 'lax')

# Method/attribute call names whose results are device values in this
# codebase: the engine's jitted executables and device-side helpers.
_DEVICE_CALL_NAMES = frozenset(
    {
        '_sample_device',
        '_sample',
        '_merge_ids',
        '_put',
        '_put_many',
        '_scatter_tokens',
        '_write_prefill',
        '_cow_copy',
    }
)
_DEVICE_CALL_SUFFIXES = ('_window', '_fn', '_paged', '_prefill')


def _func_name(func: ast.AST) -> str | None:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _dotted_root(node: ast.AST) -> str | None:
    """The leftmost name of a dotted expression (``jnp`` for
    ``jnp.sum(x)``, ``self`` for ``self._decode_window``)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _static_attr_leaves(expr: ast.AST) -> set[ast.AST]:
    """AST nodes under a static attribute access (``x.shape`` etc.):
    reading these never concretizes the array, so a name seen only there
    must neither trip a branch check nor propagate trackedness."""
    leaves: set[ast.AST] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            for leaf in ast.walk(node.value):
                leaves.add(leaf)
    return leaves


# --------------------------------------------------- traced-function index
class TracedIndex:
    """Which functions in a module are jit/pallas-reachable.

    Seeds: ``@jax.jit``-style decorators, ``jax.jit(f)`` / ``pallas_call
    (f, ...)`` wrap sites anywhere in the module, and ``# distlint:
    traced`` markers on ``def`` lines. The closure step marks every
    module-local function referenced *by name* from a traced function's
    body — deliberately reference-based, not call-based, so scan/cond
    bodies passed as values are reached.
    """

    _JIT_NAMES = frozenset({'jit', 'pjit'})
    # Unary wrappers: the function operand is args[0] (plus pallas_call's
    # kernel= keyword).
    _WRAP_NAMES = frozenset({'jit', 'pjit', 'pallas_call', 'checkpoint',
                             'remat', 'custom_vjp', 'vmap', 'grad',
                             'shard_map', 'scan'})
    # Control-flow combinators take function operands at varying
    # positions (while_loop(cond, body), fori_loop(lo, hi, body),
    # cond(pred, true_fn, false_fn), switch(i, [branches...])) — every
    # argument that resolves to a module function is seeded; the other
    # operands are arrays and cannot collide with function names.
    _CONTROL_FLOW_NAMES = frozenset({'cond', 'while_loop', 'fori_loop',
                                     'switch'})

    @classmethod
    def for_source(cls, source: SourceFile) -> 'TracedIndex':
        """Per-file cache: both traced rules share one index build."""
        cached = getattr(source, '_traced_index', None)
        if cached is None:
            cached = source._traced_index = cls(source)
        return cached

    def __init__(self, source: SourceFile):
        self.functions: dict[str, ast.AST] = {}
        by_name: dict[str, list[str]] = {}
        for qual, node in source.functions():
            self.functions[qual] = node
            by_name.setdefault(node.name, []).append(qual)
        traced: set[str] = set()
        marker_lines = source.markers('traced')
        for qual, node in self.functions.items():
            if node.lineno in marker_lines:
                traced.add(qual)
            for deco in node.decorator_list:
                if self._is_jit_expr(deco):
                    traced.add(qual)
        # `k = functools.partial(f, ...)` / `k = f` bindings anywhere in
        # the module, so a wrap site spelled `pallas_call(k, ...)` still
        # seeds `f` (the repo's real kernels bind the partial on its own
        # line before the call).
        aliases: dict[str, str] = {}
        for node in source.nodes():
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
                continue
            tgt = node.targets[0]
            if not isinstance(tgt, ast.Name):
                continue
            value = node.value
            if (
                isinstance(value, ast.Call)
                and _func_name(value.func) == 'partial'
                and value.args
            ):
                value = value.args[0]
            if isinstance(value, ast.Name) and value.id != tgt.id:
                aliases[tgt.id] = value.id
        for node in source.nodes():
            if not isinstance(node, ast.Call):
                continue
            name = _func_name(node.func)
            if name in self._WRAP_NAMES:
                candidates = list(node.args[:1]) + [
                    kw.value for kw in node.keywords if kw.arg == 'kernel'
                ]
            elif name in self._CONTROL_FLOW_NAMES:
                candidates = []
                for arg in node.args:
                    if isinstance(arg, (ast.List, ast.Tuple)):
                        candidates.extend(arg.elts)  # switch branch lists
                    else:
                        candidates.append(arg)
            else:
                continue
            for arg in candidates:
                # Unwrap functools.partial(kernel, ...) wrap sites.
                if (
                    isinstance(arg, ast.Call)
                    and _func_name(arg.func) == 'partial'
                    and arg.args
                ):
                    arg = arg.args[0]
                if isinstance(arg, ast.Name):
                    target = arg.id
                    for _ in range(len(aliases)):
                        if target in by_name or target not in aliases:
                            break
                        target = aliases[target]
                    traced.update(by_name.get(arg.id, ()))
                    traced.update(by_name.get(target, ()))
        # Transitive same-module closure over name references.
        changed = True
        while changed:
            changed = False
            for qual in list(traced):
                node = self.functions.get(qual)
                if node is None:
                    continue
                for ref in ast.walk(node):
                    if not isinstance(ref, ast.Name):
                        continue
                    for callee in by_name.get(ref.id, ()):
                        if callee not in traced and callee != qual:
                            traced.add(callee)
                            changed = True
        self.traced = traced

    def _is_jit_expr(self, deco: ast.AST) -> bool:
        name = _func_name(deco)
        if name in self._JIT_NAMES:
            return True
        if isinstance(deco, ast.Call):
            callee = _func_name(deco.func)
            if callee in self._JIT_NAMES:
                return True
            if callee == 'partial' and deco.args:
                return _func_name(deco.args[0]) in self._JIT_NAMES
        return False

    def traced_functions(self):
        for qual in sorted(self.traced):
            yield qual, self.functions[qual]


def _fixpoint_derived_names(fn: ast.AST, expr_is_derived) -> set[str]:
    """The shared derived-name fixpoint: repeatedly sweep ``fn``'s
    assignments (Assign / AugAssign / AnnAssign / walrus), marking every
    target name whose value ``expr_is_derived(expr, derived)`` judges
    derived, until no new names appear. Both trackers (traced-value and
    device-value) are this loop with a different predicate — keep them
    from diverging by keeping the machinery in one place."""
    derived: set[str] = set()
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            targets: list[ast.AST] = []
            value = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                if node.value is None:
                    continue
                targets, value = [node.target], node.value
            elif isinstance(node, ast.NamedExpr):
                targets, value = [node.target], node.value
            else:
                continue
            if not expr_is_derived(value, derived):
                continue
            for tgt in targets:
                for leaf in ast.walk(tgt):
                    if (
                        isinstance(leaf, ast.Name)
                        and leaf.id not in derived
                    ):
                        derived.add(leaf.id)
                        changed = True
    return derived


def _isinstance_arg_names(expr: ast.AST) -> set[ast.AST]:
    """``ast.Name`` nodes appearing inside ``isinstance(...)`` arguments.
    ``isinstance`` inspects the PYTHON type of its operand — for traced
    code that is the pytree-container class (the ``QuantizedKV``-vs-bare-
    array dispatch in ops/paged_attention.py), resolved at trace time and
    never concretizing a tracer — so these occurrences are static exactly
    like ``.shape``/``.dtype`` attribute reads."""
    names: set[ast.AST] = set()
    for node in ast.walk(expr):
        if (
            isinstance(node, ast.Call)
            and _dotted_root(node.func) == 'isinstance'
        ):
            for arg in node.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name):
                        names.add(sub)
    return names


def _identity_test_names(expr: ast.AST) -> set[ast.AST]:
    """``ast.Name`` operands of an ``is`` / ``is not`` comparison: an
    identity test (``q_lens is None``) reads which Python object a name is
    bound to at trace time and never concretizes a tracer."""
    names: set[ast.AST] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            names.update(
                operand for operand in (node.left, *node.comparators)
                if isinstance(operand, ast.Name)
            )
    return names


def _jnp_derived_names(fn: ast.AST) -> set[str]:
    """Names bound (directly or transitively) from ``jnp.*``/``lax.*``/
    ``jax.*`` expressions inside ``fn``. Parameters are deliberately NOT
    assumed traced — branching on config objects threaded through traced
    code is normal; only locally device-derived values are tracked."""

    def expr_is_derived(expr: ast.AST, derived: set[str]) -> bool:
        # isinstance results are static bools (trace-time type dispatch),
        # so `quantized = isinstance(cache, QuantizedKV)` must not mark
        # `quantized` as device-derived.
        statics = _static_attr_leaves(expr) | _isinstance_arg_names(expr)
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                root = _dotted_root(node.func)
                if root in _DEVICE_MODULES:
                    return True
            elif (
                isinstance(node, ast.Name)
                and node.id in derived
                and node not in statics
            ):
                return True
        return False

    return _fixpoint_derived_names(fn, expr_is_derived)


def _test_uses_traced_value(test: ast.AST, derived: set[str]) -> bool:
    """True when evaluating ``test`` concretizes a tracked array: either
    a direct ``jnp.*``/``lax.*`` call, or a tracked name used as a value
    (not merely via a static attribute like ``.shape`` or an
    ``isinstance`` type dispatch)."""
    static_bases = (
        _static_attr_leaves(test) | _isinstance_arg_names(test)
        | _identity_test_names(test)
    )
    for node in ast.walk(test):
        if isinstance(node, ast.Call):
            root = _dotted_root(node.func)
            if root in ('jnp', 'lax'):
                return True
        if (
            isinstance(node, ast.Name)
            and node.id in derived
            and node not in static_bases
        ):
            return True
    return False


@register
class TracedPythonBranchRule(Rule):
    """No Python ``if``/``while``/``assert`` on a traced array value
    inside jit/pallas-reachable functions — concretizing a tracer either
    raises at trace time or silently freezes one branch into the
    executable. Use ``jnp.where`` / ``lax.cond`` / ``lax.while_loop``."""

    id = 'traced-python-branch'
    description = 'Python control flow on a traced array value'

    def applies(self, source: SourceFile) -> bool:
        return self.in_package(source)

    def check(self, source: SourceFile, project: Project):
        index = TracedIndex.for_source(source)
        for qual, fn in index.traced_functions():
            derived = _jnp_derived_names(fn)
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While)):
                    kind = 'if' if isinstance(node, ast.If) else 'while'
                    if _test_uses_traced_value(node.test, derived):
                        yield self.diag(
                            source,
                            node.lineno,
                            f'`{kind}` on a traced array value in traced '
                            f'function {qual!r} — use lax.cond/'
                            'lax.while_loop/jnp.where',
                        )
                elif isinstance(node, ast.Assert):
                    if _test_uses_traced_value(node.test, derived):
                        yield self.diag(
                            source,
                            node.lineno,
                            f'`assert` on a traced array value in traced '
                            f'function {qual!r} — use '
                            'checkify or a static check',
                        )


@register
class NondeterminismInDispatchRule(Rule):
    """No ``time.*`` / ``random.*`` / ``np.random.*`` calls inside traced
    functions: they run once at trace time, baking that sample into the
    compiled executable — the code reads as dynamic but is frozen, and a
    silent recompile resamples it. Use ``jax.random`` with explicit keys
    (device-side) or hoist the host call out of the traced region."""

    id = 'nondeterminism-in-dispatch'
    description = 'host time/random call inside a traced function'

    _ROOTS = frozenset({'time', 'random'})

    def applies(self, source: SourceFile) -> bool:
        return self.in_package(source)

    def check(self, source: SourceFile, project: Project):
        index = TracedIndex.for_source(source)
        for qual, fn in index.traced_functions():
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if not isinstance(func, ast.Attribute):
                    continue
                root = _dotted_root(func)
                spelled = None
                if root in self._ROOTS:
                    spelled = f'{root}.{func.attr}'
                elif (
                    root in ('np', 'numpy')
                    and isinstance(func.value, ast.Attribute)
                    and func.value.attr == 'random'
                ):
                    spelled = f'{root}.random.{func.attr}'
                if spelled is not None:
                    yield self.diag(
                        source,
                        node.lineno,
                        f'{spelled}() inside traced function {qual!r} '
                        'runs once at trace time and bakes a constant '
                        'into the executable',
                    )


# ------------------------------------------------------ host-sync-in-hot-path
@register
class HostSyncInHotPathRule(Rule):
    """No blocking device→host transfer inside the engine window loop or
    a model dispatch path. Flags ``np.asarray``/``np.array``/
    ``jax.device_get`` calls, ``.item()``/``.tolist()``/
    ``.block_until_ready()`` method calls, and ``float()``/``int()``/
    ``bool()`` of a device-derived value. The designed fetch points (one
    per processed window) carry justified suppressions — everything else
    is a stray sync that re-serializes the pipelined dispatch stream."""

    id = 'host-sync-in-hot-path'
    description = 'blocking device→host sync inside a serving hot path'

    # Built-in hot-path designations: the engine window loop and the
    # model-side dispatch entry points. Extend with `# distlint:
    # hot-path` on a def line.
    HOT_PATHS: dict[str, tuple[str, ...]] = {
        'distllm_tpu/generate/engine/engine.py': (
            'LLMEngine.step',
            'LLMEngine._dispatch_window',
            'LLMEngine._dispatch_spec_window',
            'LLMEngine._process_window',
            'LLMEngine._process_spec_window',
            'LLMEngine._process_chunk_entries',
            'LLMEngine._run_to_completion',
            # The pipelined loop body behind _run_to_completion's
            # recovery wrapper (ISSUE 15), plus the recovery/deadline
            # helpers that run between windows: none may add a stray
            # sync (time.sleep backoff is host-only, not a device sync).
            'LLMEngine._serve_pipelined',
            'LLMEngine._recover',
            'LLMEngine._expire_deadlines',
            'LLMEngine._sample_device',
            'LLMEngine._window_reserve',
            'LLMEngine._window_budget',
            'LLMEngine._budget_left',
            # The decode-budget gate runs at every admission attempt of
            # both loops (scheduler.py "Admission by decode budget"):
            # host arithmetic over at most max_num_seqs rows.
            'LLMEngine._decode_budget_admits',
            'LLMEngine._budget_row',
            'LLMEngine._head_waits_on_inflight',
            'LLMEngine._reserve_shortfall',
            # KV-tier spill/promotion (docs/prefix_caching.md "Tier
            # hierarchy"): runs inside the serving loop under pool
            # pressure. Exactly three designed syncs — the spill's K/V
            # fetch pair and the promotion-completion probe — each with
            # a justified suppression; anything else added here would
            # re-serialize the async prefetch the tier exists for.
            'LLMEngine._spill_blocks',
            'LLMEngine._spill_chunk',
            'LLMEngine._begin_promotion',
            'LLMEngine._finish_promotions',
            'LLMEngine._evict_cached_blocks',
            # Quantize-at-write landing site (docs/serving.md "Quantized
            # KV cache"): the prefill scatter that computes per-block
            # absmax scales on device. Entirely jit-traced — any host
            # sync added here would fire per admitted prefill.
            '_write_prefill_all_layers',
            '_write_prefill_all_layers_quantized',
            '_gather_blocks_all_layers',
        ),
        'distllm_tpu/models/mistral.py': (
            'mixed_window',
            'spec_window',
            'decode_step',
            'decode_loop',
            'prefill_paged',
        ),
        # What every served family's window and layer walk go through (the
        # step scan that was ``mistral.decode_loop``'s, since PR 44): traced
        # into every decode dispatch of every family.
        'distllm_tpu/models/common.py': (
            'decode_window',
            'layer_at',
            'finish_layer',
            'last_token',
        ),
        # The quantize-at-write / rescale-on-append path (docs/serving.md
        # "Quantized KV cache"): these run inside every traced serving
        # dispatch that touches an int8 pool, so a stray sync here
        # serializes every window — same contract as the engine loop.
        'distllm_tpu/ops/paged_attention.py': (
            'decode_attention',
            'quantize_kv_rows',
            '_rescale_int8_blocks',
            '_gather_kv_blocks',
            'write_token_kv',
            '_write_token_kv_quantized',
            'write_chunk_kv',
            '_write_chunk_kv_quantized',
            'write_prefill_kv',
            '_write_prefill_kv_quantized',
        ),
        # Device sampling and sampled speculative verification
        # (docs/speculative.md "Sampled verification"): these trace into
        # every decode/mixed/spec dispatch, so any host sync here fires
        # once per window — the packed verify result has exactly one
        # audited fetch point in the engine, not inside these kernels.
        'distllm_tpu/ops/sampling.py': (
            'fold_row_keys',
            'filter_logits',
            'sample_tokens',
            'sample_tokens_windowed',
            'verify_spans',
        ),
        # Peer KV handoff (docs/routing.md "Peer KV tier"): the tier walk
        # and the fabric fetch/serve run inside the serving loop's
        # promotion path (and, server-side, concurrent WITH a sibling's
        # loop). All host/zmq/numpy work by design — a device sync added
        # here would stall a replica on its PEER's traffic.
        'distllm_tpu/generate/engine/kv_cache.py': (
            'PeerKVTier.contains',
            'PeerKVTier.get',
            'HostKVTier.lookup',
            'HostKVTier.get',
            'HostKVTier.contains_local',
            'HostKVTier.encoded_local',
        ),
        'distllm_tpu/parallel/fabric.py': (
            'KVBlockServer._serve',
            'KVBlockClient.request',
        ),
    }

    _SYNC_CALLS = frozenset({'asarray', 'array', 'device_get'})
    _SYNC_METHODS = frozenset({'item', 'tolist', 'block_until_ready'})
    _CASTS = frozenset({'float', 'int', 'bool'})

    def applies(self, source: SourceFile) -> bool:
        return self.in_package(source)

    def check_project(self, project: Project):
        """Every HOT_PATHS entry must resolve to a real function — a
        rename would otherwise silently drop hot-path coverage, the same
        silent-rot class the suppression-unused audit closes for
        directives. Files absent from a path-subset run are skipped."""
        for rel, prefixes in self.HOT_PATHS.items():
            source = project.file(rel)
            if source is None or source.tree is None:
                continue
            bases = {
                qual.split('.<locals>.')[0] for qual, _ in source.functions()
            }
            for prefix in prefixes:
                if prefix not in bases:
                    yield Diagnostic(
                        rule_id=self.id,
                        path=rel,
                        line=1,
                        message=(
                            f'HOT_PATHS entry {prefix!r} resolves to no '
                            'function in this file — stale after a '
                            'rename; update HostSyncInHotPathRule.'
                            'HOT_PATHS or coverage silently shrinks'
                        ),
                    )

    def _hot_functions(self, source: SourceFile):
        prefixes = self.HOT_PATHS.get(source.rel, ())
        marker_lines = source.markers('hot-path')
        hot: list[tuple[str, ast.AST]] = []
        for qual, node in source.functions():
            base = qual.split('.<locals>.')[0]
            if base in prefixes or node.lineno in marker_lines:
                hot.append((qual, node))
        # Nested functions inherit their enclosing hot path (the window
        # loop's process_one/drain_one closures) — handled by the
        # `.split('.<locals>.')[0]` base match above.
        return hot

    @staticmethod
    def _device_derived_names(fn: ast.AST) -> set[str]:
        """Names bound from device-producing calls: jnp/jax expressions,
        the engine's jitted executables (``self._decode_window`` et al.),
        and anything derived from those. An ``np.asarray(...)`` result is
        HOST data — the sync is flagged at the asarray itself, and
        downstream ``int()`` of the host copy is free."""

        def call_is_device(node: ast.Call) -> bool:
            root = _dotted_root(node.func)
            if root in _DEVICE_MODULES:
                return not (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in ('device_get',)
                )
            name = _func_name(node.func)
            if name is None:
                return False
            if name in _DEVICE_CALL_NAMES:
                return True
            return name.endswith(_DEVICE_CALL_SUFFIXES)

        def expr_is_derived(expr: ast.AST, derived: set[str]) -> bool:
            statics = _static_attr_leaves(expr)
            for node in ast.walk(expr):
                if isinstance(node, ast.Call):
                    name = _func_name(node.func)
                    root = _dotted_root(node.func)
                    if root in ('np', 'numpy') or name in ('asarray',):
                        return False  # host copy: tracking stops here
                    if call_is_device(node):
                        return True
                elif (
                    isinstance(node, ast.Name)
                    and node.id in derived
                    and node not in statics
                ):
                    return True
            return False

        return _fixpoint_derived_names(fn, expr_is_derived)

    @staticmethod
    def _host_derived_names(fn: ast.AST) -> set[str]:
        """Names bound from host copies (``np.*``/``asarray`` results and
        anything derived from those with no device data flowing in).
        ``.item()``/``.tolist()`` of these is free — the sync already
        happened (and was flagged or suppressed) at the fetch point."""

        def expr_is_derived(expr: ast.AST, derived: set[str]) -> bool:
            has_host = False
            for node in ast.walk(expr):
                if isinstance(node, ast.Call):
                    root = _dotted_root(node.func)
                    name = _func_name(node.func)
                    if root in ('np', 'numpy') or name == 'asarray':
                        has_host = True
                    elif (
                        root in _DEVICE_MODULES
                        or name in _DEVICE_CALL_NAMES
                        or (name or '').endswith(_DEVICE_CALL_SUFFIXES)
                    ):
                        return False  # device data flows in
                elif isinstance(node, ast.Name) and node.id in derived:
                    has_host = True
            return has_host

        return _fixpoint_derived_names(fn, expr_is_derived)

    def check(self, source: SourceFile, project: Project):
        seen: set[int] = set()
        for qual, fn in self._hot_functions(source):
            derived = self._device_derived_names(fn)
            host = self._host_derived_names(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if id(node) in seen:
                    continue
                seen.add(id(node))
                func = node.func
                # np.asarray / np.array / jax.device_get
                if (
                    isinstance(func, ast.Attribute)
                    and func.attr in self._SYNC_CALLS
                    and _dotted_root(func) in ('np', 'numpy', 'jax')
                ):
                    yield self.diag(
                        source,
                        node.lineno,
                        f'{_dotted_root(func)}.{func.attr}() in hot path '
                        f'{qual!r} blocks on device→host transfer',
                    )
                    continue
                # .item() / .tolist() / .block_until_ready() — skipped
                # when the receiver is a pure host copy (the sync already
                # happened at the tracked-and-suppressed fetch point);
                # unknown receivers stay flagged, conservatively.
                if (
                    isinstance(func, ast.Attribute)
                    and func.attr in self._SYNC_METHODS
                    and not node.args
                ):
                    receiver = func.value
                    recv_host = any(
                        isinstance(leaf, ast.Name) and leaf.id in host
                        for leaf in ast.walk(receiver)
                    )
                    recv_device = any(
                        isinstance(leaf, ast.Name) and leaf.id in derived
                        for leaf in ast.walk(receiver)
                    )
                    if recv_host and not recv_device:
                        continue
                    yield self.diag(
                        source,
                        node.lineno,
                        f'.{func.attr}() in hot path {qual!r} blocks on '
                        'device→host transfer',
                    )
                    continue
                # jax.block_until_ready(x) function form
                if (
                    isinstance(func, ast.Attribute)
                    and func.attr == 'block_until_ready'
                ):
                    yield self.diag(
                        source,
                        node.lineno,
                        f'block_until_ready() in hot path {qual!r} '
                        'blocks the dispatch stream',
                    )
                    continue
                # float()/int()/bool() of a device-derived value
                if (
                    isinstance(func, ast.Name)
                    and func.id in self._CASTS
                    and len(node.args) == 1
                ):
                    arg = node.args[0]
                    uses_device = any(
                        isinstance(leaf, ast.Name) and leaf.id in derived
                        for leaf in ast.walk(arg)
                    ) or any(
                        isinstance(leaf, ast.Call)
                        and _dotted_root(leaf.func) in _DEVICE_MODULES
                        for leaf in ast.walk(arg)
                    )
                    if uses_device:
                        yield self.diag(
                            source,
                            node.lineno,
                            f'{func.id}() of a device value in hot path '
                            f'{qual!r} forces a blocking transfer',
                        )


# ------------------------------------------------------------ lock-discipline
@register
class LockDisciplineRule(Rule):
    """Attributes annotated ``# guarded by self.<lock>`` on their
    assignment line may only be read or written inside a ``with
    self.<lock>:`` block in the same class — a static race detector for
    state shared between the engine thread, the aiohttp event loop, and
    watchdog threads. Constructors (``__init__``/``__new__``) are exempt
    (the object is not yet shared); a method *called with the lock held*
    documents that with ``# guarded by self.<lock>`` on its ``def``
    line."""

    id = 'lock-discipline'
    description = 'guarded attribute touched outside its lock'

    def applies(self, source: SourceFile) -> bool:
        return self.in_package(source)

    def check(self, source: SourceFile, project: Project):
        annotations = source.guarded_annotations()
        if not annotations:
            return
        assert source.tree is not None
        for node in source.nodes():
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(source, node, annotations)

    @staticmethod
    def _with_holds_lock(node: ast.With, lock: str) -> bool:
        for item in node.items:
            expr = item.context_expr
            if (
                isinstance(expr, ast.Attribute)
                and expr.attr == lock
                and isinstance(expr.value, ast.Name)
                and expr.value.id == 'self'
            ):
                return True
        return False

    _CONSTRUCTORS = frozenset({'__init__', '__new__'})

    def _check_class(self, source, cls: ast.ClassDef, annotations):
        # attr -> lock name, discovered from annotated self.X assignments
        # anywhere in the class. Exempt: constructors (the object is not
        # yet shared) and methods whose DEF line carries the annotation
        # (documented as called with the lock held). The annotation
        # itself exempts NOTHING outside a constructor — an unlocked
        # write that carries `# guarded by self._lock` both declares the
        # guard and violates it, and letting the declaration silence the
        # finding would be an unaudited suppression channel.
        guarded: dict[str, str] = {}
        exempt_methods: set[ast.AST] = set()
        methods = [
            node
            for node in cls.body
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for method in methods:
            if annotations.get(method.lineno):
                exempt_methods.add(method)  # def-line: callers hold it
            if method.name in self._CONSTRUCTORS:
                exempt_methods.add(method)
            for node in ast.walk(method):
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                    targets = [node.target]
                for tgt in targets:
                    if not (
                        isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == 'self'
                    ):
                        continue
                    end = getattr(node, 'end_lineno', tgt.lineno)
                    lock = annotations.get(tgt.lineno) or annotations.get(end)
                    if lock and lock != tgt.attr:
                        guarded[tgt.attr] = lock
        if not guarded:
            return
        for method in methods:
            if method in exempt_methods:
                continue
            yield from self._check_method(source, cls, method, guarded)

    def _check_method(self, source, cls, method, guarded):
        locked_lines: dict[str, set[int]] = {}
        for node in ast.walk(method):
            if not isinstance(node, ast.With):
                continue
            for lock in set(guarded.values()):
                if not self._with_holds_lock(node, lock):
                    continue
                lines = set(
                    range(node.lineno, (node.end_lineno or node.lineno) + 1)
                )
                # A closure DEFINED under the lock executes LATER,
                # without it — the watchdog-timer-callback race class.
                # Its body lines are not lock-covered.
                for inner in ast.walk(node):
                    if isinstance(
                        inner,
                        (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef),
                    ):
                        lines -= set(
                            range(
                                inner.lineno,
                                (inner.end_lineno or inner.lineno) + 1,
                            )
                        )
                locked_lines.setdefault(lock, set()).update(lines)
        for node in ast.walk(method):
            if not (
                isinstance(node, ast.Attribute)
                and node.attr in guarded
                and isinstance(node.value, ast.Name)
                and node.value.id == 'self'
            ):
                continue
            lock = guarded[node.attr]
            if node.lineno in locked_lines.get(lock, ()):
                continue
            yield self.diag(
                source,
                node.lineno,
                f'{cls.name}.{method.name} touches self.{node.attr} '
                f'(guarded by self.{lock}) outside `with self.{lock}:`',
            )
