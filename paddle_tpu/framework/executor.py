"""Executor + Scope.

TPU-native replacement for the reference Executor stack:
- `Executor::Run` hot loop (/root/reference/paddle/fluid/framework/executor.cc:449)
- the Python feed/fetch façade (python/paddle/fluid/executor.py:676)
- ParallelExecutor/graph passes (framework/parallel_executor.cc) — subsumed
  by XLA: the whole program becomes ONE jitted function, so fusion, memory
  planning and scheduling belong to the compiler, and the per-op dynamic
  dispatch loop only exists at trace time.

Execution model: a Program's op list is interpreted once while tracing; the
traced function `step(state, feeds, rng) -> (new_state, fetches)` is jitted
with state-buffer donation (the analogue of the reference's in-place
variable mutation).  BackwardSection markers (see program.py) are realized
with jax.value_and_grad over the preceding forward segment.

Scope maps variable names to device arrays (parity: framework/scope.h:46,
minus the parent-chain — programs here resolve names at trace time).
"""

import contextlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import flags
from ..core.dtype import to_jax_dtype
from ..core.place import default_place
from ..ops.registry import get_op
from .compiler import apply_precision_policy, resolve_precision
from .program import Variable, default_main_program

_profiler = None
_monitor = None
_resilience = None
_op_sampler_slot = None
_flight = None
_fleet_mod = None
_goodput_mod = None


def _dispatch_span(name):
    """profiler.RecordEvent span when a profiling session is active,
    else a no-op context — the steady-state dispatch path must not grow
    the profiler's event list on every step of a long training run."""
    global _profiler
    if _profiler is None:
        from .. import profiler

        _profiler = profiler
    if _profiler.is_profiling():
        return _profiler.RecordEvent(name)
    return contextlib.nullcontext()


def _mon():
    """Lazy paddle_tpu.monitor handle (same import-cycle discipline as
    _profiler): the telemetry subsystem Executor.run feeds per-step
    metrics and compile events into while monitor.is_enabled()."""
    global _monitor
    if _monitor is None:
        from .. import monitor

        _monitor = monitor
    return _monitor


def _res():
    """Lazy paddle_tpu.resilience handle: anomaly guard, retry policy,
    preemption flag, and the fault-injection harness the dispatch path
    consults.  When nothing is enabled the whole fault-tolerance layer
    costs the steady state three None checks per run."""
    global _resilience
    if _resilience is None:
        from .. import resilience

        _resilience = resilience
    return _resilience


def _sampler():
    """Active per-op sampler (monitor.op_profile.sampling scope) or
    None — resolved through the module's single-slot list so the
    interpreter loop pays one list load per op while sampling is off."""
    global _op_sampler_slot
    if _op_sampler_slot is None:
        from ..monitor import op_profile

        _op_sampler_slot = op_profile._ACTIVE
    return _op_sampler_slot[0]


def _fr():
    """The always-on flight recorder (monitor.flight_recorder): a
    bounded ring of step/compile/recovery records that costs one deque
    append per step while healthy and dumps a post-mortem on crash."""
    global _flight
    if _flight is None:
        from ..monitor import flight_recorder

        _flight = flight_recorder.get()
    return _flight


def _fleet():
    """Lazy paddle_tpu.monitor.fleet handle (ISSUE 10): rank identity,
    the dp timestamp feeds, and the skew ring the straggler probe's
    gathered wait vectors land in."""
    global _fleet_mod
    if _fleet_mod is None:
        from ..monitor import fleet

        _fleet_mod = fleet
    return _fleet_mod


def _gp():
    """Lazy paddle_tpu.monitor.goodput handle (ISSUE 20): the run
    ledger the dispatch path charges wall time into.  goodput.active()
    is None unless FLAGS_goodput armed one — the whole off path is one
    module-global read."""
    global _goodput_mod
    if _goodput_mod is None:
        from ..monitor import goodput

        _goodput_mod = goodput
    return _goodput_mod


# reusable (contextlib.nullcontext is reentrant) — the off path must
# not allocate a context object per span site
_NULL_CTX = contextlib.nullcontext()


def _gspan(category):
    """Goodput span context for `category`: a real ledger span while a
    run ledger is active, the shared nullcontext otherwise."""
    gled = _gp().active()
    if gled is None:
        return _NULL_CTX
    return gled.span(category)


def _goodput_batches(gen):
    """Iterate `gen` charging the wait for each next prepared batch to
    the active ledger's data_wait bucket (reader / prefetch / sparse-
    pull starvation as seen by the consuming thread); a plain
    passthrough when no ledger is active."""
    gen = iter(gen)
    end = object()
    while True:
        gled = _gp().active()
        if gled is None:
            item = next(gen, end)
        else:
            with gled.span("data_wait"):
                item = next(gen, end)
        if item is end:
            return
        yield item


def _materialize(fetches):
    """Block on device fetches and copy them to host numpy arrays — the
    ONE sync point of the dispatch path.  Every host materialization the
    executor performs goes through here so the no-sync steady-state
    contract of train_from_dataset is testable (a counting wrapper over
    this function observes every sync)."""
    return [np.asarray(f) for f in fetches]


class Scope:
    """name -> array store for persistable variables."""

    def __init__(self):
        self.vars = {}

    def find_var(self, name):
        return self.vars.get(name)

    def var(self, name):
        return self.vars.setdefault(name, None)

    def set_var(self, name, value):
        self.vars[name] = value

    def drop_kids(self):
        self.vars.clear()

    def local_var_names(self):
        return list(self.vars)


_global_scope = Scope()


def global_scope():
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _global_scope
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old

    return guard()


class _RngBox:
    """Mutable PRNG key holder threaded through op interpretation."""

    __slots__ = ("key",)

    def __init__(self, key):
        self.key = key

    def next(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def _resolve_slot(env, names):
    vals = []
    for n in names:
        if n not in env:
            raise KeyError(
                f"variable '{n}' has no value: not fed, not initialized "
                f"(did you run the startup program?)"
            )
        vals.append(env[n])
    if len(vals) == 1:
        return vals[0]
    return vals


# Ops whose outputs are trace-time constants (static attrs only). Their
# concrete numpy values are tracked in a side const_env so that ops with
# value-dependent output SHAPES (range, linspace) can still resolve under
# jit — the analogue of the reference's compile-time shape inference for
# fill_constant-fed shape ops.
_CONST_EVAL = {
    "fill_constant": lambda ins, attrs: {
        "Out": np.full(tuple(attrs.get("shape", [])),
                       float(attrs.get("value", 0.0)))},
    "assign_value": lambda ins, attrs: {
        "Out": np.array(
            attrs.get("fp32_values") or attrs.get("int32_values")
            or attrs.get("int64_values") or attrs.get("bool_values")
        ).reshape(attrs.get("shape"))},
}

# Ops that need CONCRETE input values (output shape depends on them).
_NEEDS_CONST_INPUTS = {"range", "linspace"}

# Ops with data-dependent output shapes: impossible under jit by
# construction (XLA static shapes); they work in the eager executor.
_DYNAMIC_SHAPE_OPS = {"where_index", "masked_select", "unique",
                      "shrink_memory"}


def _branch_env(env):
    # lists are tensor-arrays with trace-time mutation semantics; both
    # lax.cond branches get traced, so they must NOT share the outer list
    return {k: (list(v) if isinstance(v, list) else v)
            for k, v in env.items()}


def _branch_fn(ops, env, key, out_names, const_env=None):
    """Interpret a sub-block against a copy of the outer env, returning
    the named results — the body of a lax.cond/while/scan closure. `key`
    seeds a branch-local RngBox so rng draws inside the traced closure
    never mutate the outer box with an inner-trace tracer."""
    def fn(bound, key=key):
        benv = _branch_env(env)
        benv.update(bound)
        box = _RngBox(key)
        interpret(ops, benv, box, const_env)
        return tuple(benv[n] for n in out_names)

    return fn


def _run_cond(op, env, rng_box, const_env=None):
    """conditional_block pair -> lax.cond (layers/control_flow.py cond)."""
    program = op.block.program
    a = op.attrs
    pred = env[op.inputs["Pred"][0]]
    pred = jnp.asarray(pred).reshape(())
    t_ops = program.blocks[a["true_block"]].ops
    f_ops = program.blocks[a["false_block"]].ops
    k = rng_box.next()  # outer-level split; branches fold a branch id in
    outs = jax.lax.cond(
        pred,
        lambda _: _branch_fn(t_ops, env, jax.random.fold_in(k, 0),
                             a["true_outs"], const_env)({}),
        lambda _: _branch_fn(f_ops, env, jax.random.fold_in(k, 1),
                             a["false_outs"], const_env)({}),
        None)
    for n, v in zip(op.outputs["Out"], outs):
        env[n] = v


def _run_switch(op, env, rng_box, const_env=None):
    """Switch -> right-folded lax.cond chain (layers Switch parity:
    first true case wins, else default, else values unchanged)."""
    program = op.block.program
    a = op.attrs
    out_names = a["out_names"]
    for n in out_names:
        if n not in env:
            raise KeyError(
                f"Switch writes '{n}' but it has no value before the "
                f"switch (cases only run conditionally)")
    k = rng_box.next()
    result = tuple(env[n] for n in out_names)
    if a.get("default_block") is not None:
        d_ops = program.blocks[a["default_block"]].ops
        # branch id past all case ids; fold_in rejects negative ints
        result = _branch_fn(d_ops, env,
                            jax.random.fold_in(k, len(a["case_blocks"])),
                            out_names, const_env)({})
    for i in range(len(a["case_blocks"]) - 1, -1, -1):
        pred = jnp.asarray(env[a["case_preds"][i]]).reshape(())
        c_ops = program.blocks[a["case_blocks"][i]].ops
        taken = _branch_fn(c_ops, env, jax.random.fold_in(k, i),
                           out_names, const_env)
        result = jax.lax.cond(pred, lambda _, t=taken: t({}),
                              lambda _, r=result: r, None)
    for n, v in zip(op.outputs["Out"], result):
        env[n] = v


def _run_while(op, env, rng_box, const_env=None):
    """while_op.cc -> lax.while_loop."""
    program = op.block.program
    a = op.attrs
    loop_names = op.inputs["LoopVars"]
    init_vars = tuple(jnp.asarray(env[n]) for n in loop_names)
    c_ops = program.blocks[a["cond_block"]].ops
    b_ops = program.blocks[a["body_block"]].ops
    # rng key rides in the carry so each iteration draws fresh randomness
    init = init_vars + (rng_box.next(),)

    def cond_fn(carry):
        (out,) = _branch_fn(c_ops, env, carry[-1], [a["cond_out"]],
                            const_env)(dict(zip(a["cond_inner"],
                                                carry[:-1])))
        return jnp.asarray(out).reshape(())

    def body_fn(carry):
        key, sub = jax.random.split(carry[-1])
        outs = _branch_fn(b_ops, env, sub, a["body_outs"], const_env)(
            dict(zip(a["body_inner"], carry[:-1])))
        return tuple(jnp.asarray(o, init_vars[i].dtype)
                     for i, o in enumerate(outs)) + (key,)

    max_iters = a.get("max_iters")
    if max_iters:
        # bounded lowering onto lax.scan so reverse-mode AD works (the
        # while_grad parity path): iterate max_iters times, freezing the
        # carry once the condition goes false
        def scan_body(carry, _):
            run = cond_fn(carry)
            new = body_fn(carry)
            frozen = tuple(jnp.where(run, n, c)
                           for n, c in zip(new[:-1], carry[:-1]))
            return frozen + (new[-1],), None

        outs, _ = jax.lax.scan(scan_body, init, None, length=int(max_iters))
    else:
        outs = jax.lax.while_loop(cond_fn, body_fn, init)
    for n, v in zip(op.outputs["Out"], outs[:-1]):
        env[n] = v


def _run_static_rnn(op, env, rng_box, const_env=None):
    """StaticRNN -> lax.scan over the leading (time) axis."""
    program = op.block.program
    a = op.attrs
    ops = program.blocks[a["block"]].ops
    xs = tuple(jnp.asarray(env[n]) for n in op.inputs["StepInputs"])
    init_mem = tuple(jnp.asarray(env[n]) for n in op.inputs["InitMemories"])
    init = init_mem + (rng_box.next(),)

    def body(carry, x_t):
        key, sub = jax.random.split(carry[-1])
        bound = dict(zip(a["memory_inner"], carry[:-1]))
        bound.update(zip(a["input_inner"], x_t))
        outs = _branch_fn(ops, env, sub,
                          list(a["memory_update"]) + list(a["step_outs"]),
                          const_env)(bound)
        n_mem = len(a["memory_update"])
        new_carry = tuple(jnp.asarray(o, init_mem[i].dtype)
                          for i, o in enumerate(outs[:n_mem]))
        return new_carry + (key,), tuple(outs[n_mem:])

    _, stacked = jax.lax.scan(body, init, xs)
    for n, v in zip(op.outputs["Out"], stacked):
        env[n] = v


def _array_index(name, env, const_env):
    v = env.get(name)
    try:
        return int(np.asarray(v))
    except Exception:
        if const_env is not None and name in const_env:
            return int(np.asarray(const_env[name]))
        raise NotImplementedError(
            "tensor-array indices must be compile-time constants under "
            "the jitted executor (use while_loop/scan state for dynamic "
            "indexing, or FLAGS_eager_executor)")


def _run_array_op(op, env, rng_box, const_env=None):
    """LoDTensorArray ops: trace-time python-list semantics. The index
    must be trace-time static under jit (use while_loop/scan otherwise)."""
    t = op.type
    if t == "create_array":
        env[op.outputs["Out"][0]] = []
        return
    if t == "array_write":
        arr = env[op.inputs["Array"][0]]
        i = _array_index(op.inputs["I"][0], env, const_env)
        x = env[op.inputs["X"][0]]
        if i == len(arr):
            arr.append(x)
        elif i < len(arr):
            arr[i] = x
        else:
            raise IndexError(f"array_write index {i} > length {len(arr)}")
        return
    if t == "array_read":
        arr = env[op.inputs["Array"][0]]
        i = _array_index(op.inputs["I"][0], env, const_env)
        env[op.outputs["Out"][0]] = arr[i]
        return
    if t == "array_length":
        arr = env[op.inputs["Array"][0]]
        env[op.outputs["Out"][0]] = jnp.asarray(len(arr), jnp.int32)
        return
    if t in ("lod_tensor_to_array", "array_to_lod_tensor"):
        # row counts are value-dependent -> concrete values only, same
        # contract as _DYNAMIC_SHAPE_OPS but routed via the array table
        import jax.core as _core

        probe = jax.tree.leaves(
            [env.get(n) for names in op.inputs.values() for n in names])
        if any(isinstance(v, _core.Tracer) for v in probe):
            raise NotImplementedError(
                f"op '{t}' has data-dependent output shapes and cannot "
                f"run under the jitted executor; set "
                f"FLAGS_eager_executor=1 for this program")
    if t == "lod_tensor_to_array":
        # control_flow.py:1132 parity: split [B, T, ...] into
        # per-timestep slices over the rank-table's still-active prefix.
        # Row counts are value-dependent -> concrete lengths only
        # (FLAGS_eager_executor), like the reference's LoD machinery.
        x = np.asarray(env[op.inputs["X"][0]])
        table = np.asarray(env[op.inputs["RankTable"][0]])
        order, lengths = table[:, 0].astype(int), table[:, 1]
        max_len = int(lengths[0]) if len(lengths) else 0
        out = []
        for t_step in range(max_len):
            active = int((lengths > t_step).sum())
            out.append(jnp.asarray(x[order[:active], t_step]))
        env[op.outputs["Out"][0]] = out
        return
    if t == "array_to_lod_tensor":
        # control_flow.py:1174 parity: inverse of the split above,
        # restoring original row order and right-padding short rows
        arr = env[op.inputs["X"][0]]
        table = np.asarray(env[op.inputs["RankTable"][0]])
        order, lengths = table[:, 0].astype(int), table[:, 1]
        b = len(order)
        max_len = len(arr)
        feat = np.asarray(arr[0]).shape[1:] if arr else ()
        dtype = np.asarray(arr[0]).dtype if arr else np.float32
        out = np.zeros((b, max_len) + tuple(feat), dtype)
        for t_step, step_rows in enumerate(arr):
            step_rows = np.asarray(step_rows)
            active = step_rows.shape[0]
            out[order[:active], t_step] = step_rows
        env[op.outputs["Out"][0]] = jnp.asarray(out)
        return


def _run_while_block(op, env, rng_box, const_env=None):
    """Block-style While (the reference's while_op used via
    fluid.layers.While): loop state is every outer variable the body
    block assigns, plus the condition variable; iteration stops when the
    body's assign to the condition goes false."""
    program = op.block.program
    a = op.attrs
    body = program.blocks[a["body_block"]]
    cond_name = a["cond_name"]
    written = set()
    for o in body.ops:
        written.update(o.output_names())
    carry_names = sorted({cond_name} | {n for n in written if n in env})
    cond_pos = carry_names.index(cond_name)
    init = tuple(jnp.asarray(env[n]) for n in carry_names) \
        + (rng_box.next(),)

    def cond_fn(carry):
        return jnp.asarray(carry[cond_pos]).reshape(()).astype(bool)

    def body_fn(carry):
        key, sub = jax.random.split(carry[-1])
        local = _branch_env(env)
        local.update(dict(zip(carry_names, carry[:-1])))
        interpret(body.ops, local, _RngBox(sub), const_env)
        return tuple(jnp.asarray(local[n], init[i].dtype)
                     for i, n in enumerate(carry_names)) + (key,)

    max_iters = a.get("max_iters")
    if max_iters:
        # bounded lax.scan lowering so reverse-mode AD can flow through
        # the loop (same contract as the functional while_loop op)
        def scan_body(carry, _):
            run = cond_fn(carry)
            new = body_fn(carry)
            frozen = tuple(jnp.where(run, n, c)
                           for n, c in zip(new[:-1], carry[:-1]))
            return frozen + (new[-1],), None

        outs, _ = jax.lax.scan(scan_body, init, None,
                               length=int(max_iters))
    else:
        outs = jax.lax.while_loop(cond_fn, body_fn, init)
    for n, v in zip(carry_names, outs[:-1]):
        env[n] = v


# the single definition shared with the PT201 lint and the DCE pass
# (analysis/facts.py): an op type added there must survive _live_ops
# pruning too, or its side effect is silently dropped on fetch-pruned
# runs while the lint still calls it live
from ..analysis.facts import SIDE_EFFECT_TYPES as _SIDE_EFFECT_OPS

_CONTROL_FLOW_OPS = {
    "cond": _run_cond,
    "switch": _run_switch,
    "while_loop": _run_while,
    "while_block": _run_while_block,
    "static_rnn": _run_static_rnn,
    "create_array": _run_array_op,
    "array_write": _run_array_op,
    "array_read": _run_array_op,
    "array_length": _run_array_op,
    "lod_tensor_to_array": _run_array_op,
    "array_to_lod_tensor": _run_array_op,
}


def run_op(op, env, rng_box, const_env=None, scope=None):
    """Execute one recorded op against env (used at trace time).

    With `scope` ("{section}/{op_type}_{idx}", see op_scopes), the
    whole emission — control-flow sub-traces included — runs inside
    jax.named_scope(scope), so every HLO instruction this op stages
    carries its ProgramDesc identity in metadata.op_name (the
    provenance monitor.op_profile attributes device cost by).  Pure
    trace-time cost: compiled steps never re-enter here."""
    if scope is not None:
        with jax.named_scope(scope):
            return _run_op(op, env, rng_box, const_env)
    return _run_op(op, env, rng_box, const_env)


def _run_op(op, env, rng_box, const_env=None):
    if op.type in _CONTROL_FLOW_OPS:
        _CONTROL_FLOW_OPS[op.type](op, env, rng_box, const_env)
        return
    opdef = get_op(op.type)
    ins = {}
    for slot, names in op.inputs.items():
        if not names:
            continue
        ins[slot] = _resolve_slot(env, names)
    attrs = op.attrs
    if opdef.needs_rng:
        attrs = dict(attrs)
        attrs["_rng"] = rng_box.next()
    if flags.flag("executor_log_ops"):
        print(f"[paddle_tpu.executor] {op.type} {list(op.inputs)} -> {list(op.outputs)}")

    if op.type in _NEEDS_CONST_INPUTS and const_env is not None:
        const_ins = {}
        ok = True
        for slot, names in op.inputs.items():
            if not names:
                continue
            if all(n in const_env for n in names):
                vals = [const_env[n] for n in names]
                const_ins[slot] = vals[0] if len(vals) == 1 else vals
            else:
                ok = False
        if ok:
            # keep as numpy: jnp.asarray would stage a tracer under jit
            ins = {k: np.asarray(v) for k, v in const_ins.items()}
        else:
            raise NotImplementedError(
                f"op '{op.type}' has a value-dependent output shape; its "
                f"inputs must be compile-time constants under the jitted "
                f"executor (or use FLAGS_eager_executor)")
    elif op.type in _DYNAMIC_SHAPE_OPS:
        import jax.core as _core

        if any(isinstance(v, _core.Tracer)
               for v in jax.tree.leaves(ins)):
            raise NotImplementedError(
                f"op '{op.type}' has a data-dependent output shape and "
                f"cannot run under the jitted executor; set "
                f"FLAGS_eager_executor=1 for this program")

    try:
        outs = opdef.fn(ins, attrs)
    except Exception as e:
        # decorate with the op identity + creation site (the reference
        # attaches the Python stack to op errors, op_call_stack.cc)
        where = getattr(op, "callsite", None)
        note = (f"[operator '{op.type}' "
                f"(inputs {list(op.inputs)}, outputs {list(op.outputs)})"
                + (f", created at {where}" if where else "") + "]")
        if hasattr(e, "add_note"):
            e.add_note(note)
            raise
        try:
            decorated = type(e)(f"{e} {note}")
        except Exception:
            # exception classes with non-str __init__ (UnicodeDecodeError
            # etc.) can't be reconstructed from a message — re-raise as-is
            raise e
        raise decorated from e
    for slot, names in op.outputs.items():
        if slot not in outs:
            continue
        vals = outs[slot]
        if len(names) == 1 and not isinstance(vals, (list, tuple)):
            env[names[0]] = vals
        else:
            vals = vals if isinstance(vals, (list, tuple)) else [vals]
            for n, v in zip(names, vals):
                env[n] = v
    if const_env is not None and op.type in _CONST_EVAL:
        try:
            c_outs = _CONST_EVAL[op.type](ins, attrs)
            for slot, names in op.outputs.items():
                if slot in c_outs:
                    const_env[names[0]] = c_outs[slot]
        except Exception:
            pass


def interpret(ops, env, rng_box, const_env=None, scopes=None,
              allow_sampling=True, pins=None):
    """Run `ops` in order.  `scopes` maps id(op) -> scope name (built
    once per program by op_scopes); while a monitor.op_profile sampler
    is active (the eager/dygraph sampling mode), each op is wall-timed
    with block_until_ready on its outputs and recorded under its scope
    — plus a profiler span when a profiling session is on, so the
    chrome trace grows per-op rows.  allow_sampling=False marks a
    jit-STAGING caller (_make_step_fn): its per-op durations would be
    pure trace time masquerading as measurements, so the sampler is
    bypassed there even when active.

    `pins` ({var_name: NamedSharding}, GSPMD tier) constrains each
    listed var right after the op producing it — the activation-edge
    with_sharding_constraint insertion of the lowered ShardingPlan."""
    sampler = _sampler() if allow_sampling else None
    if sampler is None:
        for op in ops:
            run_op(op, env, rng_box, const_env,
                   scopes.get(id(op)) if scopes else None)
            if pins:
                _apply_pins(op, env, pins)
        return
    global _profiler
    if _profiler is None:
        from .. import profiler

        _profiler = profiler
    for op in ops:
        scope = (scopes.get(id(op)) if scopes else None) \
            or f"main/{op.type}"
        t0 = time.perf_counter_ns()
        run_op(op, env, rng_box, const_env, scope)
        if pins:
            _apply_pins(op, env, pins)
        outs = [env[n] for n in op.output_names() if n in env]
        try:
            # concrete arrays block until device-done (the honest per-op
            # time); tracers under an autodiff/jit trace have nothing to
            # block on and record host dispatch time instead
            jax.block_until_ready(outs)
        except Exception:
            pass
        t1 = time.perf_counter_ns()
        sampler.note(scope, (t1 - t0) / 1e3)
        _profiler.add_span(scope, t0, t1)


def _apply_pins(op, env, pins):
    """Constrain `op`'s just-produced outputs listed in `pins` — the
    trace-time with_sharding_constraint emission of the GSPMD tier.
    Scoped under the op's own named_scope caller, so the pin's HLO
    carries the same provenance as the op it anchors."""
    for n in op.output_names():
        s = pins.get(n)
        if s is not None and n in env:
            env[n] = jax.lax.with_sharding_constraint(env[n], s)


def op_scopes(ops, sections):
    """Deterministic per-op scope names for one live-op list:
    "{section}/{op_type}_{idx}" with idx the op's position in the list
    and section fwd<k> for ops feeding backward section k, update for
    ops after the last section (optimizer/stats), main when the
    program has no backward sections.  Derived from program structure
    alone, so names are STABLE across recompiles of the same program
    (the property the attribution tests pin)."""
    section_ends = [(bs.pos, f"fwd{k}") for k, bs in enumerate(sections)]
    tail = "update" if sections else "main"
    names = []
    for i, op in enumerate(ops):
        prefix = tail
        for pos, name in section_ends:
            if i < pos:
                prefix = name
                break
        names.append(f"{prefix}/{op.type}_{i}")
    return names


def op_scope_names(program, fetch_names=(), train_loop=False):
    """Public provenance map for one program: [(scope, op)] in
    execution order, exactly the scopes the compiled step will emit —
    what monitor.op_profile checks attribution coverage against.

    With FLAGS_graph_opt=on the executor traces the OPTIMIZED
    substitute, so the map resolves through it: fused/folded ops appear
    under their own (emitted) scopes and carry ``op.folded_from`` — the
    source ops' scope names — so attribution tools can map device time
    on a rewritten op back to what the user built instead of landing it
    in ``(unattributed)``.  ``train_loop=True`` additionally resolves
    the FLAGS_amp / FLAGS_graph_opt_fuse train tier exactly as a
    ``train_from_dataset`` dispatch would (their "train" default only
    fires on that path)."""
    if hasattr(program, "_get_executable_program"):
        program = program._get_executable_program()
    do_amp, do_fuse = Executor._train_tier_modes(program, train_loop)
    if do_amp or do_fuse:
        program = Executor._resolve_train_optimized(
            program, list(fetch_names), do_amp, do_fuse)
    if flags.flag("graph_opt") == "on":
        program = Executor._resolve_optimized(program, list(fetch_names))
    ops = Executor._live_ops(program, list(fetch_names))
    sections = [] if program._is_test else list(program.backward_sections)
    return list(zip(op_scopes(ops, sections), ops))


def _checkpoint_chunks(seg, checkpoint_names):
    """Split a forward segment at the ops producing each checkpoint var.
    Returns [(ops, remat?)]: chunks between checkpoints are wrapped in
    jax.checkpoint (recompute) — parity with the recompute_segments of
    backward.py:639."""
    if not checkpoint_names:
        return [(seg, False)]
    ckpts = set(checkpoint_names)
    boundaries = []
    for i, op in enumerate(seg):
        if set(op.output_names()) & ckpts:
            boundaries.append(i + 1)
    if not boundaries:
        return [(seg, False)]
    chunks = []
    start = 0
    for b in boundaries:
        if seg[start:b]:
            chunks.append((seg[start:b], True))
        start = b
    if seg[start:]:
        chunks.append((seg[start:], False))
    return chunks


class _RunPlan:
    """Steady-state dispatch analysis for one (program, version).

    The Fluid reference keeps its hot loop fast by doing program
    analysis once (feed/fetch-targeted pruning, executor.py:236/274);
    the per-call analogue here — the persist-name list, the
    produced/read op-name sets, and the feed-name -> dtype map — is
    computed ONCE per program mutation so a cached-hit Executor.run is
    a dict lookup plus one compiled call, with no list_vars() scan.

    The plan is stored on the Program itself (program._run_plan_cache),
    so a recycled id() of a garbage-collected program can never alias
    another program's plan; `version` pins it to the _version counter
    every graph mutation bumps (Block.append_op / create_var), and
    `program` guards against a foreign plan object being rebound onto
    a different Program instance."""

    __slots__ = ("program", "version", "persist_names", "produced",
                 "read_names", "_feed_dtypes")

    def __init__(self, program):
        self.program = program
        self.version = program._version
        self.persist_names = tuple(sorted(
            v.name for v in program.list_vars() if v.persistable))
        produced, read = set(), set()
        for op in program.global_block().ops:
            produced.update(op.output_names())
            read.update(op.input_names())
        self.produced = produced
        self.read_names = read
        self._feed_dtypes = {}

    def feed_dtype(self, name):
        """Declared jax dtype of a feed var (None when undeclared) —
        resolved through the block chain once per name, then served
        from the plan."""
        try:
            return self._feed_dtypes[name]
        except KeyError:
            v = self.program.global_block()._find_var_recursive(name)
            dt = to_jax_dtype(v.dtype) if v is not None and v.dtype else None
            self._feed_dtypes[name] = dt
            return dt


class Executor:
    """Parity: fluid.Executor (executor.py:437)."""

    def __init__(self, place=None):
        self.place = place or default_place()
        self._cache = {}
        seed = flags.flag("global_seed")
        self._root_key = jax.random.PRNGKey(seed)
        # True while scope state may hold arrays committed to devices
        # a dp mesh doesn't cover (fresh executor over a user-restored
        # scope; re-armed by checkpoint restore paths).  Gates the dp
        # re-placement scan so the steady-state dispatch path never
        # pays per-var sharding checks.
        self._check_state_placement = True
        # GSPMD runtime tier (ISSUE 16): memoized ShardingPlan per
        # (program, version, rule fingerprint, feed shapes), and a
        # placement stamp per program so the per-leaf sharded
        # device_put scan runs once per (program, mesh, rules) — the
        # steady-state dispatch pays one dict probe.
        self._spmd_plans = {}
        self._spmd_place_stamps = {}

    def close(self):
        self._cache.clear()

    @staticmethod
    def _get_plan(program, use_program_cache=True):
        """The program's run-plan: served from program._run_plan_cache
        on a (same program, same _version) hit, rebuilt otherwise.
        use_program_cache=False bypasses the cache entirely — neither
        reads nor stores it (the same contract as the compiled-fn
        cache)."""
        mon = _mon()
        if use_program_cache:
            plan = getattr(program, "_run_plan_cache", None)
            if plan is not None and plan.program is program \
                    and plan.version == program._version:
                if mon.is_enabled():
                    mon.counter("run_plan.hit").add(1)
                return plan
        if mon.is_enabled():
            mon.counter("run_plan.miss").add(1)
        plan = _RunPlan(program)
        if use_program_cache:
            program._run_plan_cache = plan
        return plan

    def _get_spmd_plan(self, program, rules, fetch_names, feed_arrays):
        """Memoized ShardingPlan for the GSPMD tier: one
        ``analysis.sharding.lower`` per (program identity, version,
        rule fingerprint, feed shapes) — a rule re-attachment or a
        feed-shape change re-lowers, the steady state pays a dict
        probe.  Entries hold the program so a recycled id() after GC
        can't serve a stale plan."""
        shapes = {n: tuple(np.shape(a)) for n, a in feed_arrays.items()
                  if not n.startswith("__fleet_")}
        key = (id(program), program._version, rules.fingerprint(),
               tuple(sorted(shapes.items())), tuple(fetch_names))
        ent = self._spmd_plans.get(key)
        if ent is not None and ent[0] is program:
            return ent[1]
        from ..analysis import sharding as _sh

        plan = _sh.lower(program, rules, fetch_names=fetch_names,
                         feed_names=sorted(shapes),
                         feed_shapes=shapes)
        if len(self._spmd_plans) >= 8:
            self._spmd_plans.clear()
        self._spmd_plans[key] = (program, plan)
        return plan

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
        use_program_cache=True,
        _train_loop=False,
    ):
        # Goodput accounting (ISSUE 20): while a run ledger is active,
        # the whole dispatch body is a host_dispatch span — re-labeled
        # compile on a fresh trace, with the device-sync points inside
        # charging productive_step (innermost span wins).  With no
        # ledger (FLAGS_goodput off) this is one global read and a
        # direct call into the unchanged dispatch path.
        gled = _gp().active()
        if gled is None:
            return self._run_impl(program, feed, fetch_list, scope,
                                  return_numpy, use_program_cache,
                                  _train_loop)
        pushed = gled.push("host_dispatch")
        try:
            return self._run_impl(program, feed, fetch_list, scope,
                                  return_numpy, use_program_cache,
                                  _train_loop)
        finally:
            if pushed:
                gled.pop()

    def _run_impl(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
        use_program_cache=True,
        _train_loop=False,
    ):
        program = program if program is not None else default_main_program()
        mon = _mon()
        mon_on = mon.is_enabled()
        # t0 is read unconditionally: the always-on flight recorder's
        # minimal step record wants the dispatch time too (one clock
        # read — far under the <2% fast-path budget)
        t0 = time.perf_counter_ns()
        # CompiledProgram / parallel wrapper support
        dp_mesh = None
        dp_key = None
        precision = resolve_precision(program)
        telemetry_key = getattr(program, "_telemetry_label", None)
        spmd_rules = None
        spmd_plan = None
        if hasattr(program, "_get_executable_program"):
            if getattr(program, "_is_spmd", False):
                # GSPMD runtime tier (ISSUE 16): the attached partition
                # rules EXECUTE — state placed per-leaf on the rule
                # mesh, model axes handed to XLA as auto axes, the dp
                # axis staying the manual grad-sync axis below.
                spmd_rules = program._spmd_rules
                dp_mesh = program._spmd_mesh()
                if "dp" not in dp_mesh.axis_names \
                        or spmd_rules.data_axis != "dp":
                    raise ValueError(
                        "executable sharding rules need a 'dp' data "
                        "axis on the mesh (size 1 is fine); got axes "
                        f"{dp_mesh.axis_names} with data axis "
                        f"{spmd_rules.data_axis!r}")
                # rule fingerprint + mesh device identity: re-attaching
                # rules or retargeting the mesh retraces instead of
                # serving a stale layout
                dp_key = program._spmd_key()
            elif getattr(program, "_is_data_parallel", False):
                dp_mesh = program._dp_mesh()
                # device-IDENTITY key (memoized with the mesh): an
                # elastic retarget_dp onto a same-sized different
                # device set must retrace, not reuse the dead world's
                # executable
                dp_key = program._dp_mesh_key()
            program = program._get_executable_program()
        if telemetry_key is None:
            telemetry_key = getattr(program, "_telemetry_label", None)
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope if scope is not None else _global_scope

        fetch_names = [
            f.name if isinstance(f, Variable) else str(f) for f in fetch_list
        ]

        # Performance tier (ISSUE 14): bf16 AMP rewrite + fused-kernel
        # pattern matching on a cloned substitute, in the canonical
        # order AMP rewrite -> fusion -> structural passes (the
        # graph_opt substitution below composes third).  FLAGS_amp /
        # FLAGS_graph_opt_fuse default "train": they fire for programs
        # dispatched by train_from_dataset (the zoo train path) and
        # stay out of bare Executor.run unless set to "on" — with both
        # "off", this costs two flag reads and the dispatch path is
        # byte-for-byte the pre-fusion executor.
        do_amp, do_fuse = self._train_tier_modes(program, _train_loop)
        if do_amp or do_fuse:
            tier_opt = self._resolve_train_optimized(
                program, fetch_names, do_amp, do_fuse)
            if tier_opt is not program:
                # mirror the CURRENT sharding-rule attachment (same
                # contract as the graph_opt substitution below): a
                # re-attached or removed rule set must not keep linting
                # a cached substitute against stale rules
                rules = getattr(program, "_sharding_rules", None)
                if getattr(tier_opt, "_sharding_rules", None) is not \
                        rules:
                    tier_opt._sharding_rules = rules
            program = tier_opt

        # Graph-optimizer substitution (FLAGS_graph_opt=on): trace the
        # OPTIMIZED twin of the program — CSE/const-fold/identity/DCE
        # applied by paddle_tpu.passes — cached per (version, fetches,
        # pass config) on the program, so a flag flip or a pass-config
        # change re-optimizes while the steady state pays one flag
        # read + one dict probe.  The substitute is a different object
        # with its own _version, so the run-plan and compiled-step
        # caches key on the pass config for free.
        if flags.flag("graph_opt") == "on":
            opt = self._resolve_optimized(program, fetch_names)
            if opt is not program:
                # the substitute is a clone — mirror the CURRENT
                # sharding-rule attachment (analysis metadata, not
                # graph state) so the PT3xx lints neither vanish under
                # graph_opt=on nor keep linting a cached clone against
                # rules the user has since replaced or removed
                rules = getattr(program, "_sharding_rules", None)
                if getattr(opt, "_sharding_rules", None) is not rules:
                    opt._sharding_rules = rules
            program = opt

        # Optimize-time-folded constants become initialized
        # persistables; their values live on the program — seed them
        # into the scope so both the compiled and eager paths resolve
        # them like any other persistable state.  Stamped per
        # (program, version): a re-optimized program OVERWRITES its
        # stale constants instead of first-write-wins serving them,
        # and the steady state pays one getattr compare.
        fc = getattr(program, "_folded_constants", None)
        if fc:
            # per-(program, version) seed memo on the scope, so an
            # alternating train/eval pair doesn't re-device-put its
            # constants every step.  Entries hold the PROGRAM, not its
            # id(): a recycled address after GC must not make a new
            # program's constants look already-seeded (same defense as
            # the compiled-step cache storing the program in its
            # value).
            stamps = getattr(scope, "_folded_seed_stamps", None)
            if stamps is None:
                stamps = scope._folded_seed_stamps = {}
            ent = stamps.get(id(program))
            if ent is None or ent[0] is not program \
                    or ent[1] != program._version:
                for n, v in fc.items():
                    scope.set_var(n, jnp.asarray(v))
                if len(stamps) >= 8:
                    stamps.clear()
                stamps[id(program)] = (program, program._version)

        # Static program verification (FLAGS_static_check=off|warn|error):
        # the pre-trace InferShape/def-use/donation/dp lint pass of
        # paddle_tpu.analysis.  Results are cached per (program,
        # _version, fetches, feeds, dp) — _bump() invalidates — so the
        # steady-state dispatch path pays one flag read + one dict
        # probe; "off" (the default) costs the flag read alone.
        check_mode = flags.flag("static_check")
        if check_mode and check_mode != "off":
            self._static_check(program, fetch_names, feed, dp_mesh,
                               check_mode, telemetry_key, mon, mon_on,
                               dp_ndev=(int(dp_mesh.shape["dp"])
                                        if spmd_rules is not None
                                        else None))

        res = _res()
        guard = res.active_guard()
        # the fused finite check only exists where loss/grads exist:
        # train programs with backward sections on the compiled path
        guard_on = (guard is not None and not program._is_test
                    and bool(program.backward_sections))

        with _dispatch_span("executor.run.prepare"):
            plan = self._get_plan(program, use_program_cache)

            feed_arrays = {}
            feed_casts = {}
            for name, value in feed.items():
                dtype = plan.feed_dtype(name)
                if isinstance(value, jax.Array):
                    # already on device (reader.device_prefetch path): a
                    # mismatched dtype is cast INSIDE the compiled step
                    # (feed_casts), so the prefetched buffer costs the
                    # dispatch path neither a host round-trip nor a
                    # separate per-call cast dispatch
                    if dtype is not None and value.dtype != dtype:
                        feed_casts[name] = dtype
                    feed_arrays[name] = value
                else:
                    feed_arrays[name] = jnp.asarray(np.asarray(value),
                                                    dtype=dtype)
            if spmd_rules is not None:
                # lower the rules into the executable ShardingPlan
                # (state placement, activation pins, model-collective
                # records) — memoized per (program, version, rule
                # fingerprint, feed shapes), so the steady state pays
                # one dict probe
                spmd_plan = self._get_spmd_plan(
                    program, spmd_rules, fetch_names, feed_arrays)
            if res.faultinject.is_armed():
                # fault-injection harness: counts this dispatch and may
                # hand back a NaN-tainted COPY of the feed dict (the
                # caller's arrays are never touched, so a rollback
                # replay of the same batch sees clean data)
                feed_arrays = res.faultinject.on_step_feed(feed_arrays)
                # latency/hang injection (fleet straggler smoke): the
                # stall happens BEFORE the skew probe's timestamp is
                # taken, so an injected slow rank looks exactly like a
                # real one to the barrier-wait attribution
                res.faultinject.stall_point("executor.step")

            self._root_key, run_key = jax.random.split(self._root_key)

        if flags.flag("eager_executor") or flags.flag("check_nan_inf"):
            # the debug path must execute at the SAME precision as the
            # compiled step it stands in for, or the numerics being
            # hunted (e.g. a NaN under check_nan_inf) need not reproduce.
            # It interprets op-by-op, so feed casts happen up front.
            if feed_casts:
                feed_arrays = {
                    n: (a.astype(feed_casts[n]) if n in feed_casts else a)
                    for n, a in feed_arrays.items()}
            out = apply_precision_policy(
                lambda: self._run_eager(program, feed_arrays, fetch_names,
                                        scope, run_key, return_numpy),
                precision)()
            step_rec = None
            if mon_on:
                # the debug interpreter EXECUTES inline — elapsed time
                # here is execution, not dispatch, so no
                # host_dispatch_us is recorded (it would contaminate
                # the dispatch aggregates ~1000x)
                step_rec = self._record_step_metrics(mon, None,
                                                     feed_arrays, out)
            fr = _fr()
            if fr.enabled:
                fr.note_step(step_rec)
            return out

        with _dispatch_span("executor.run.state"):
            state = {}
            missing = []
            for n in plan.persist_names:
                val = scope.find_var(n)
                if val is None:
                    missing.append(n)
                else:
                    state[n] = val
            # Vars never written before and not produced by this program
            # are an error only if some op reads them; let interpretation
            # raise lazily.
            state_names = tuple(sorted(state))
            for n in missing:
                if n not in plan.produced and n in plan.read_names:
                    raise RuntimeError(
                        f"persistable variable '{n}' is uninitialized; run "
                        f"the startup program first"
                    )

            if spmd_plan is not None:
                # per-leaf SHARDED placement (the tentpole's HBM win):
                # params and the donated optimizer state go onto the
                # rule mesh under their lowered NamedSharding — an
                # mp-sharded leaf's per-shard bytes shrink by ~1/mp.
                # Scanned once per (program, mesh identity, rule
                # fingerprint) via the placement stamp, re-armed by the
                # restore paths through _check_state_placement.
                stamp = self._spmd_place_stamps.get(id(program))
                if (self._check_state_placement or stamp is None
                        or stamp[0] is not program
                        or stamp[1] != dp_key):
                    from jax.sharding import (NamedSharding,
                                              PartitionSpec as _P)

                    for n, v in state.items():
                        spec = spmd_plan.state_specs.get(n)
                        sh = NamedSharding(
                            dp_mesh,
                            spec.to_jax() if spec is not None else _P())
                        if getattr(v, "sharding", None) != sh:
                            state[n] = jax.device_put(v, sh)
                    if len(self._spmd_place_stamps) >= 8:
                        self._spmd_place_stamps.clear()
                    self._spmd_place_stamps[id(program)] = (program,
                                                            dp_key)
                    self._check_state_placement = False
            elif dp_mesh is not None and self._check_state_placement:
                # a checkpoint restore (auto_resume / guard rollback
                # into a cold scope) hands back arrays COMMITTED to the
                # template's devices; shard_map refuses committed
                # arrays that don't cover the mesh, so re-place them
                # replicated.  The scan runs only while the placement
                # flag is armed (executor construction + restore
                # paths): steady-state dispatch pays nothing for it.
                from jax.sharding import (NamedSharding,
                                          PartitionSpec as _P)

                mesh_devs = set(dp_mesh.devices.flat)
                rep = None
                for n, v in state.items():
                    devs = getattr(getattr(v, "sharding", None),
                                   "device_set", None)
                    if devs is not None and devs != mesh_devs:
                        if rep is None:
                            rep = NamedSharding(dp_mesh, _P())
                        state[n] = jax.device_put(v, rep)
                self._check_state_placement = False

            if dp_mesh is not None:
                # feeds split over the DATA axis only: the full device
                # count for pure dp, the dp-axis extent on a {dp,mp}
                # rule mesh (mp shards see the whole local batch)
                ndev = (int(dp_mesh.shape["dp"])
                        if spmd_rules is not None
                        else dp_mesh.devices.size)
                for n, a in feed_arrays.items():
                    if a.ndim == 0 or a.shape[0] % ndev != 0:
                        raise ValueError(
                            f"data-parallel feed '{n}' needs a leading "
                            f"batch dim divisible by {ndev} devices, got "
                            f"{a.shape}")

            # Fleet skew probe (ISSUE 10): dp programs carry this
            # rank's host pre-sync timestamp on device as two reserved
            # int32 feeds; the compiled step turns them into a
            # replicated per-shard barrier-wait vector returned as one
            # extra (popped) fetch.  Constant shape/dtype, so the
            # compiled-step cache key and memoized shard_map signature
            # stay stable across steps.
            fleet_on = (dp_mesh is not None
                        and flags.flag("fleet_skew"))
            if fleet_on:
                feed_arrays = _fleet().add_timestamp_feeds(feed_arrays,
                                                           dp_mesh)

            if spmd_plan is not None:
                # jax.lax.axis_index on a manual axis lowers to a
                # PartitionId op, which XLA's SPMD partitioner rejects
                # in partial-manual (auto mp) modules — so the per-dp-
                # shard rng fold happens HERE on the host, and the
                # [dp, 2] key stack ships sharded over dp instead of
                # being folded inside the body
                run_key = jax.vmap(
                    lambda i, k=run_key: jax.random.fold_in(k, i))(
                    jnp.arange(int(dp_mesh.shape["dp"]),
                               dtype=jnp.uint32))

            feed_sig = tuple(
                (n, feed_arrays[n].shape, str(feed_arrays[n].dtype))
                for n in sorted(feed_arrays)
            )

            key = (id(program), plan.version, feed_sig, tuple(fetch_names),
                   state_names,
                   (dp_key or dp_mesh.shape_tuple)
                   if dp_mesh is not None else None,
                   precision, guard_on,
                   # the grad-sync bucket capacity is read at TRACE
                   # time (transpiler.collective.sync_gradients), so a
                   # flag change must retrace dp steps — key on it for
                   # dp programs only (non-dp traces never read it)
                   None if dp_mesh is None
                   else int(flags.flag("dp_bucket_bytes")))
            # cache value holds the program so id() can't be recycled by a
            # new Program allocated at the same address after GC
            entry = self._cache.get(key) if use_program_cache else None
        fresh_compile = entry is None or entry[1] is not program
        gled = _gp().active()
        if gled is not None and fresh_compile:
            # jit compiles on FIRST INVOCATION, so trace + XLA compile
            # both happen between here and the end of the dispatch
            # block: re-label the enclosing host_dispatch span until
            # then (time already charged stays host_dispatch — the
            # plan/feed prep above really was dispatch work)
            gled.retag("compile")
        if fresh_compile:
            if mon_on:
                mon.counter("compiled_step.miss").add(1)
            else:
                # with telemetry on, the compile ledger mirrors its
                # (fully analyzed) event into the recorder; off, this
                # marker still timestamps the recompile in a post-mortem
                fr = _fr()
                if fr.enabled:
                    fr.note_compile_marker(
                        telemetry_key or "prog%x" % id(program))
            try:
                with _dispatch_span("executor.run.trace"):
                    compiled = self._build(program, fetch_names,
                                           plan.persist_names,
                                           dp_mesh=dp_mesh,
                                           precision=precision,
                                           feed_casts=feed_casts,
                                           telemetry_key=telemetry_key,
                                           guard_on=guard_on,
                                           spmd_plan=spmd_plan)
            except Exception as e:
                # a program too big to even COMPILE dies with the same
                # RESOURCE_EXHAUSTED shape an execution OOM does
                self._oom_postmortem(e, mon_on)
                raise
            if use_program_cache:
                self._cache[key] = (compiled, program)
        else:
            if mon_on:
                mon.counter("compiled_step.hit").add(1)
            compiled = entry[0]

        try:
            with _dispatch_span("executor.run.dispatch"):
                retry_policy = res.active_retry()

                def _dispatch():
                    # an injected transient error fires here, INSIDE
                    # the retried region, so backoff + re-dispatch is
                    # the real recovery path under test
                    if res.faultinject.is_armed():
                        res.faultinject.check_transient()
                    out = compiled(state, feed_arrays, run_key)
                    if retry_policy is not None:
                        # async dispatch defers real XLA/PJRT failures
                        # to the next sync point — which would sit
                        # OUTSIDE this retried region.  With retry on,
                        # block here so a transient execution error
                        # surfaces where backoff can catch it: fault
                        # tolerance trades the steps-ahead pipeline
                        # for retryability.  The wait IS the step's
                        # device execution — goodput's productive time.
                        with _gspan("productive_step"):
                            jax.block_until_ready(out)
                    return out

                # async dispatch (retry off): this returns device
                # futures without a sync, and the donated `state`
                # buffers are rebound to the NEW device arrays — never
                # via a host copy, which would both block and
                # resurrect freed donated buffers as host memory
                if retry_policy is not None:
                    new_state, fetches = res.call_with_retry(
                        _dispatch, retry_policy)
                else:
                    new_state, fetches = _dispatch()
                for n, v in new_state.items():
                    scope.set_var(n, v)
        except Exception as e:
            # RESOURCE_EXHAUSTED is a taxonomy dump trigger: write the
            # peak-HBM post-mortem (peak table, live-bytes timeline,
            # requested-vs-device bytes, last-K steps) BEFORE the
            # error propagates — a run that died of OOM must explain
            # what was resident.  (With retry enabled an OOM is
            # retried first; only the error that finally escapes —
            # RetriesExhausted chains it — lands here.)
            self._oom_postmortem(e, mon_on)
            raise
        if gled is not None and fresh_compile:
            # compile is done (first invocation returned): the rest of
            # this run is ordinary dispatch bookkeeping again
            gled.retag("host_dispatch")
        if spmd_plan is not None:
            # record the model-axis collectives XLA inserted from the
            # auto-axis constraints: the plan's OWN implied records, so
            # last_sync_stats()["model"] equals the analyzer's table by
            # construction (the mp half of the conformance loop)
            from ..transpiler import collective as _coll

            _coll.note_model_sync(spmd_plan.model_sync_records(),
                                  key=telemetry_key)
        skew_fetch = None
        if fleet_on:
            # the skew probe's replicated wait vector rides back as the
            # very last fetch (after the guard flag); popped here,
            # handed to the fleet ring WITHOUT materializing — the
            # async dispatch pipeline never syncs on a diagnostic
            skew_fetch = fetches[-1]
            fetches = fetches[:-1]
        guard_flag = None
        if guard_on:
            # the fused all-finite flag rides back as the LAST fetch;
            # popped before metrics so fetch-byte accounting and the
            # caller's fetch list never see it
            guard_flag = fetches[-1]
            fetches = fetches[:-1]
        step_rec = None
        if mon_on:
            # recorded BEFORE any materialization so host_dispatch_us is
            # the pure dispatch cost; fetch bytes read from the device
            # array metadata (no sync).  A step that paid trace+compile
            # is tagged warmup so it can't skew the steady-state
            # aggregates (mean step time / dispatch μs / MFU).
            step_rec = self._record_step_metrics(mon, t0, feed_arrays,
                                                 fetches,
                                                 warmup=fresh_compile)
        fr = _fr()
        if fr.enabled:
            # always-on: with telemetry enabled the ring shares the
            # session's record; without it, a minimal record (one dict
            # + deque append) keeps the post-mortem window alive
            fr.note_step(step_rec,
                         host_dispatch_us=(time.perf_counter_ns() - t0)
                         / 1e3,
                         warmup=fresh_compile)
        if skew_fetch is not None:
            _fleet().note_sync(skew_fetch, step_record=step_rec,
                               mesh=dp_mesh, key=telemetry_key)
        if guard_flag is not None:
            # ONE host sync per guarded step (the policy decision needs
            # the scalar): the price of the guard, paid only when it is
            # enabled.  State selection already happened on device — an
            # anomalous step committed nothing.
            self._apply_guard_policy(res, guard, guard_flag, plan, scope)
        if return_numpy:
            with _dispatch_span("executor.run.fetch"):
                try:
                    # the one sync point of the synchronous path: the
                    # block covers the step's device execution
                    with _gspan("productive_step"):
                        return _materialize(fetches)
                except Exception as e:
                    # async dispatch (retry off) defers execution
                    # failures to this sync point — an OOM surfacing
                    # here still gets its post-mortem
                    self._oom_postmortem(e, mon_on)
                    raise
        # a fetch naming a persistable var ALIASES the buffer just bound
        # into the scope; the NEXT run donates that buffer, which would
        # invalidate a still-held device fetch.  A device-side copy (no
        # sync) decouples it — donation stays sound across the no-sync
        # steady state.
        return [jnp.copy(f) if n in new_state else f
                for n, f in zip(fetch_names, fetches)]

    @staticmethod
    def _train_tier_modes(program, train_loop):
        """(do_amp, do_fuse) for one dispatch: the ISSUE-14 performance
        tier applies only to TRAIN programs (backward sections, not a
        test clone); "train" mode further requires the dataset train
        loop (train_from_dataset), "on" covers every Executor.run.
        AMP is additionally skipped for programs the user already
        rewrote (amp_enabled)."""
        if program._is_test or not program.backward_sections:
            return False, False
        amp_mode = flags.flag("amp")
        fuse_mode = flags.flag("graph_opt_fuse")
        do_amp = (amp_mode == "on"
                  or (amp_mode == "train" and train_loop)) \
            and not program.amp_enabled
        do_fuse = (fuse_mode == "on"
                   or (fuse_mode == "train" and train_loop))
        return do_amp, do_fuse

    @staticmethod
    def _resolve_train_optimized(program, fetch_names, do_amp, do_fuse):
        """The AMP+fusion substitute for a train program — built once
        per (version, fetch set, amp dtype, fusion config) and cached
        in the same on-program ``_opt_cache`` the structural substitute
        uses (``_bump()`` clears it), so the steady-state dispatch path
        pays two flag reads and a dict probe.  Canonical order inside:
        AMP rewrite first, fusion second; the FLAGS_graph_opt
        structural tier (if on) then composes on the RESULT."""
        from .. import passes as _passes

        try:
            fuse_names = (_passes.enabled_fusion_passes()
                          if do_fuse else ())
        except KeyError as e:
            raise ValueError(
                f"FLAGS_graph_opt_fuse_disable names an unknown "
                f"fusion pass: {e}") from e
        key = ("train_tier", program._version, tuple(fetch_names),
               flags.flag("amp_dtype") if do_amp else None, fuse_names)
        cache = getattr(program, "_opt_cache", None)
        if cache:
            hit = cache.get(key)
            if hit is not None:
                return hit
        label = getattr(program, "_telemetry_label", None)
        pkey = label or "prog%x:v%d" % (id(program), program._version)
        opt = program.clone()
        if do_amp:
            from .. import amp as _amp

            _amp.rewrite_train_program(opt)
        if do_fuse:
            _passes.fuse_program(opt, fetch_names=fetch_names,
                                 clone=False, program_key=pkey)
        opt._telemetry_label = label
        # provenance for the PT4xx numerics lint and post-mortems:
        # WHICH train-tier config produced this substitute (the lint
        # runs against it — _static_check fires after this
        # substitution — and a cached substitute outlives the flag
        # state that built it)
        opt._train_tier = {
            "amp": flags.flag("amp_dtype") if do_amp else None,
            "fuse": list(fuse_names)}
        if cache is None:
            cache = program._opt_cache = {}
        elif len(cache) >= 8:
            cache.clear()
        cache[key] = opt
        return opt

    @staticmethod
    def _resolve_optimized(program, fetch_names):
        """The optimized substitute for `program` under the current
        pass config — built once per (program version, fetch set, pass
        config) and cached on the program (``_opt_cache``; ``_bump()``
        clears it, so a mutation can never serve a stale substitute).
        Value-based folds are NOT applied here: executor-run programs
        own mutable parameters, so only the structural passes are
        legal."""
        from .. import passes as _passes

        try:
            names = _passes.enabled_passes()
            # the fusion tier composes with this pipeline when
            # explicitly global — fusion FIRST (canonical order),
            # structural cleanup after.  Programs the train tier
            # already fused skip it (idempotent, but a re-scan per
            # substitute build is pure waste and its report would be
            # all-zero noise).
            fuse_names = (
                _passes.enabled_fusion_passes()
                if flags.flag("graph_opt_fuse") == "on"
                and not getattr(program, "_fusion_applied", False)
                else ())
        except KeyError as e:
            raise ValueError(
                f"FLAGS_graph_opt_disable / "
                f"FLAGS_graph_opt_fuse_disable names an unknown pass: "
                f"{e}") from e
        key = (program._version, tuple(fetch_names), names, fuse_names)
        cache = getattr(program, "_opt_cache", None)
        if cache:
            hit = cache.get(key)
            if hit is not None:
                return hit
        label = getattr(program, "_telemetry_label", None)
        pkey = label or "prog%x:v%d" % (id(program), program._version)
        src = program
        if fuse_names:
            # a separate, tier-tagged fuse_program run (not fuse_*
            # names folded into optimize_program): the telemetry
            # Fusion section keys on tier="fusion", and the structural
            # section must not absorb pattern rows
            src, _freport = _passes.fuse_program(
                program, fetch_names=fetch_names, program_key=pkey)
        opt, _report = _passes.optimize_program(
            src, fetch_names=fetch_names, passes=names,
            program_key=pkey,
            # fuse_program already cloned; don't deep-copy twice
            clone=src is program)
        opt._telemetry_label = label
        if cache is None:
            cache = program._opt_cache = {}
        elif len(cache) >= 4:
            cache.clear()
        cache[key] = opt
        return opt

    @staticmethod
    def _static_check(program, fetch_names, feed, dp_mesh, mode,
                      telemetry_key, mon, mon_on, dp_ndev=None):
        """Run the static verifier before tracing (the reference's
        build-time InferShape parity point).  A fresh analysis emits
        ONE ProgramLintWarning (warn mode), a kind="lint" telemetry
        record, and a flight-recorder event; a cache hit re-raises in
        error mode but never re-reports — a long training loop lints
        each program version exactly once."""
        from .. import analysis

        key = telemetry_key or "prog%x:v%d" % (id(program),
                                               program._version)
        result, fresh = analysis.cached_check(
            program, fetch_names=fetch_names,
            feed_names=list(feed or ()),
            dp_ndev=(dp_ndev if dp_ndev is not None
                     else None if dp_mesh is None
                     else int(dp_mesh.devices.size)),
            program_key=key)
        if fresh:
            if mon_on:
                mon.record_lint(result.to_record())
            fr = _fr()
            if fr.enabled and result.diagnostics:
                # the full kind="lint" record for post-mortem dumps
                # plus a recovery-style event marking WHEN it happened
                fr.note_lint(result.to_record())
                fr.note_event("lint", key=key,
                              errors=len(result.errors),
                              warnings=len(result.warnings),
                              codes=result.by_code())
            if result.diagnostics and (mode != "error" or result.ok):
                analysis.warn_result(result, stacklevel=4)
        if mode == "error" and not result.ok:
            raise analysis.ProgramLintError(result)

    @staticmethod
    def _oom_postmortem(exc, mon_on):
        """OOM dump trigger (resilience.taxonomy.is_oom): count the
        event and have the flight recorder write the peak-HBM
        post-mortem before the caller re-raises.  Never raises itself
        — forensics must not mask the real error."""
        try:
            if not _res().is_oom(exc):
                return
            if mon_on:
                _mon().counter("resilience.oom_events").add(1)
            fr = _fr()
            if fr.enabled:
                fr.dump_oom(exc)
        except Exception:
            pass

    @staticmethod
    def _record_step_metrics(mon, t0, feed_arrays, fetches,
                             warmup=False):
        """One telemetry step record per Executor.run: host-dispatch μs
        (entry to here; t0=None skips it — the eager debug path has no
        dispatch phase), examples (leading feed dim), feed/fetch bytes.
        Wall step time is derived by the session from the gap between
        consecutive records; warmup=True marks a run that paid
        trace+compile (excluded from steady-state means).  Returns the
        session record so the flight recorder can share it (one dict
        in both rings, no duplicate bookkeeping)."""
        examples = 0
        feed_bytes = 0
        for n, a in feed_arrays.items():
            if n.startswith("__fleet_"):
                # the skew probe's timestamp feeds are diagnostics, not
                # workload — byte/example accounting must not see them
                continue
            feed_bytes += int(getattr(a, "nbytes", 0) or 0)
            shape = getattr(a, "shape", ())
            if shape:
                examples = max(examples, int(shape[0]))
        fetch_bytes = sum(int(getattr(f, "nbytes", 0) or 0)
                          for f in fetches)
        return mon.record_step(
            host_dispatch_us=(None if t0 is None
                              else (time.perf_counter_ns() - t0) / 1e3),
            examples=examples or None, feed_bytes=feed_bytes,
            fetch_bytes=fetch_bytes, warmup=warmup)

    def _apply_guard_policy(self, res, guard, guard_flag, plan, scope):
        """Host side of the anomaly guard: read the fused finite flag
        (a float — 1.0 when every section's loss/grads were finite on
        every dp shard) and apply the active policy.

        skip_step needs no state action (the compiled step selected the
        old state on device); rollback restores the newest complete
        checkpoint into the scope and raises RollbackPerformed so the
        training loop rewinds its data cursor."""
        # the flag materialization is where the guarded step's device
        # execution is awaited: productive time — unless the policy
        # decides below that the step was wasted
        with _gspan("productive_step") as gs:
            ok = float(np.asarray(guard_flag)) >= 1.0
        if ok:
            guard.note_ok()
            return
        mon = _mon()
        if mon.is_enabled():
            mon.counter("resilience.anomaly_steps").add(1)
        fr = _fr()
        if fr.enabled:
            fr.note_event("anomaly", policy=guard.policy)
        guard.note_anomaly()         # escalates past max_consecutive
        guard.last_skipped = False
        if guard.policy == "raise":
            raise res.AnomalyError(
                "anomaly guard: non-finite loss/gradients in guarded "
                "step (policy=raise)")
        if guard.policy == "skip_step":
            guard.last_skipped = True
            if mon.is_enabled():
                mon.counter("resilience.skipped_steps").add(1)
            gled = _gp().active()
            if gled is not None:
                # the step committed nothing: the execution wait just
                # charged as productive was really recovery (sum-
                # preserving move of exactly the span's own ns)
                gled.reclassify("productive_step", "recovery",
                                getattr(gs, "ns", 0))
            return
        # rollback: restore newest complete checkpoint into the scope
        guard.note_rollback()        # escalates past max_rollbacks
        template = {}
        for n in plan.persist_names:
            v = scope.find_var(n)
            if v is not None:
                template[n] = v
        with _dispatch_span("resilience.rollback_restore"), \
                _gspan("recovery"):
            try:
                state, ck_step = guard.manager.restore_latest(template)
            except FileNotFoundError as e:
                # no complete checkpoint yet: the on-device select
                # already kept the params clean, but there is nothing
                # to roll back TO — escalate with the real story
                # instead of a bare IO error
                raise res.AnomalyError(
                    "rollback policy hit an anomaly before any complete "
                    "checkpoint existed; save one up front (train_from_"
                    "dataset does this automatically) or use "
                    "policy='skip_step'") from e
        for n, v in state.items():
            scope.set_var(n, v)
        # restored arrays may be committed off-mesh: the next dp
        # dispatch re-places them
        self._check_state_placement = True
        # checkpoints written by train_from_dataset carry the executor
        # PRNG root key: restoring it rewinds the rng STREAM along with
        # the params, so a replay of a stochastic (dropout) program is
        # bitwise-identical to the uninterrupted run
        loader = getattr(guard.manager, "load_extras", None)
        extras = loader(ck_step) if loader is not None else {}
        if "executor_rng_key" in extras:
            self._root_key = jnp.asarray(extras["executor_rng_key"])
        if mon.is_enabled():
            mon.counter("resilience.rollbacks").add(1)
        if fr.enabled:
            fr.note_event("rollback", checkpoint_step=ck_step)
        raise res.RollbackPerformed(ck_step)

    # ------------------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           sparse_config=None, _sparse_push=True,
                           prefetch=None, checkpoint=None,
                           auto_resume=False, elastic=None):
        """Dataset-driven training loop — the industrial CTR path.

        Parity: /root/reference/python/paddle/fluid/executor.py:1187
        (train_from_dataset -> _run_from_dataset -> MultiTrainer /
        HogwildWorker::TrainFiles, hogwild_worker.cc:237). The reference
        spawns N DeviceWorker threads each draining a DataFeed; here the
        native MultiSlot reader threads (csrc/data_feed.cpp) keep the
        input queue full while ONE jitted program consumes batches — on
        TPU the parallelism belongs inside the compiled step, not in
        host worker threads.

        sparse_config enables the Downpour/PS flow
        (DistMultiTrainer + DownpourWorker::TrainFiles parity —
        device_worker.h:203): {"table": SparseEmbedding-or-Communicator,
        "ids_var": slot name with ids, "emb_var": data var fed with
        pulled rows, "lr": optional} — pull before each step, push the
        embedding gradient after (the program must mark emb_var in
        append_backward's parameter_list so its @GRAD is addressable).

        prefetch: overlap batch N+1's host work (dataset iteration +
        sparse embedding pull over TCP) with batch N's device step on a
        producer thread — the reference's buffered_reader double-buffer
        (operators/reader/buffered_reader.cc) + Communicator send-overlap.
        Default (None) enables it for dense programs and for tables
        behind async/half_async/geo Communicators, where one-step-stale
        pulls are already the semantics; plain sync tables keep the
        strict pull->step->push order.

        checkpoint: fault-tolerance cadence (fleet_util save-model
        parity) — a checkpoint.CheckpointManager, a directory path, or
        a kwargs dict for CheckpointManager.  The loop saves the
        program's persistable vars every save_interval_steps, force-
        saves at the next step boundary when a preemption was requested
        (resilience.PreemptionHandler / request_preemption) and exits
        cleanly, and — when the active anomaly guard's policy is
        ``rollback`` — keeps the prepared batches since the last save
        so a rollback can replay the data cursor in place.

        auto_resume: restore the newest complete checkpoint before
        training and skip the already-consumed batches, so a re-launch
        of the SAME command continues the run (trainer-restart parity).

        elastic: an resilience.ElasticCoordinator (ISSUE 11) — its
        step_boundary hook runs before every dispatch: heartbeat +
        bounded peer sync + leave/join intents + the skew policy.  A
        topology event force-saves at THIS boundary and raises
        TopologyChanged (action "reshard_local"/"relaunch") so the
        caller rebuilds on the new world and resumes from the shared
        checkpoint; a drain (SIGUSR1) or preemption under the
        coordinator additionally posts a leave intent so survivors
        shrink without waiting out the dead-peer timeout.  The
        coordinator's manager doubles as checkpoint= when none is
        passed.

        Returns the list of final-batch fetch values (or None, like the
        reference, when fetch_list is empty).
        """
        # Goodput ledger lifecycle (ISSUE 20): one ledger per run while
        # FLAGS_goodput is on (start_run returns None otherwise, and
        # also when an enclosing run already owns the wall clock).  The
        # kind="goodput" record is emitted on EVERY exit — a run that
        # died still reports where its wall time went.
        gp = _gp()
        gled = gp.start_run(
            key=getattr(program, "_telemetry_label", None)
            or "train_from_dataset")
        if gled is None:
            return self._train_from_dataset_impl(
                program=program, dataset=dataset, scope=scope,
                thread=thread, debug=debug, fetch_list=fetch_list,
                fetch_info=fetch_info, print_period=print_period,
                sparse_config=sparse_config, _sparse_push=_sparse_push,
                prefetch=prefetch, checkpoint=checkpoint,
                auto_resume=auto_resume, elastic=elastic)
        outcome = "error"
        try:
            out = self._train_from_dataset_impl(
                program=program, dataset=dataset, scope=scope,
                thread=thread, debug=debug, fetch_list=fetch_list,
                fetch_info=fetch_info, print_period=print_period,
                sparse_config=sparse_config, _sparse_push=_sparse_push,
                prefetch=prefetch, checkpoint=checkpoint,
                auto_resume=auto_resume, elastic=elastic)
            outcome = "ok"
            return out
        finally:
            # the dp barrier wait the skew probe measured hid inside
            # the productive sync points: move it to its own bucket
            # (sum-preserving) before the record is built
            gled.fold_dp_sync(_fleet().fleet_skew())
            gp.finish_run(gled, extra={"outcome": outcome})

    def _train_from_dataset_impl(self, program=None, dataset=None,
                                 scope=None, thread=0, debug=False,
                                 fetch_list=None, fetch_info=None,
                                 print_period=100, sparse_config=None,
                                 _sparse_push=True, prefetch=None,
                                 checkpoint=None, auto_resume=False,
                                 elastic=None):
        program = program if program is not None else default_main_program()
        real_prog = program
        if hasattr(real_prog, "_get_executable_program"):
            real_prog = real_prog._get_executable_program()
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        fetch_list = list(fetch_list or [])
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list]
        fetch_info = list(fetch_info or fetch_names)
        blk = real_prog.global_block()

        # -- fault-tolerance plumbing ----------------------------------
        res = _res()
        mon = _mon()
        # live /metrics exporter (ISSUE 10): session-entry hook, never
        # per step — a no-op unless FLAGS_metrics_port says otherwise
        from ..monitor import exporter as _exporter

        _exporter.ensure_started()
        mgr = checkpoint
        if mgr is not None and not hasattr(mgr, "restore_latest"):
            from ..checkpoint import CheckpointManager

            if isinstance(mgr, str):
                mgr = CheckpointManager(mgr)
            elif isinstance(mgr, dict):
                mgr = CheckpointManager(**mgr)
            else:
                raise TypeError(
                    f"checkpoint= wants a CheckpointManager, path, or "
                    f"kwargs dict, got {type(checkpoint).__name__}")
        if elastic is not None:
            # the coordinator's manager IS the fleet's shared store:
            # the force-saves its transitions take and the loop's
            # interval saves must land in one place, or the shrink
            # path resumes from the wrong history
            if mgr is None:
                mgr = elastic.manager
            elif mgr is not elastic.manager:
                raise ValueError(
                    "checkpoint= and the elastic coordinator's manager "
                    "are different CheckpointManagers; pass the same "
                    "one so topology transitions and interval saves "
                    "share a store")
        ckpt_scope = scope if scope is not None else _global_scope
        persist_names = sorted(v.name for v in real_prog.list_vars()
                               if v.persistable)

        def _ckpt_state():
            return {n: ckpt_scope.find_var(n) for n in persist_names
                    if ckpt_scope.find_var(n) is not None}

        def _ckpt_extras():
            return {"executor_rng_key": np.asarray(self._root_key)}

        guard = res.active_guard()
        # rollback/replay only exists for TRAIN programs: an eval drain
        # (infer_from_dataset, clone(for_test=True)) is never guarded
        # (no backward sections), and adopting the guard's manager for
        # it would interval-save EVAL vars into the TRAINING store —
        # _gc would then rotate out real restore points
        is_train_prog = (not real_prog._is_test
                         and bool(real_prog.backward_sections))
        keep_replay = (guard is not None and guard.policy == "rollback"
                       and is_train_prog)
        if keep_replay:
            # the guard restores through ITS manager; the loop's saves
            # and replay numbering must point at the same store or a
            # RollbackPerformed.step means nothing here
            if mgr is None:
                mgr = guard.manager
            elif mgr is not guard.manager:
                raise ValueError(
                    "checkpoint= and the rollback guard's manager are "
                    "different CheckpointManagers; pass the same one so "
                    "rollback steps line up with the loop's saves")

        if auto_resume and mgr is None:
            raise ValueError(
                "auto_resume=True needs a checkpoint store (pass "
                "checkpoint=...); silently retraining from step 0 "
                "would re-consume data")
        start_step = 0
        if mgr is not None and auto_resume:
            template = _ckpt_state()
            if template:
                try:
                    restored, start_step = mgr.restore_latest(template)
                except FileNotFoundError:
                    start_step = 0      # cold start: nothing to resume
                else:
                    for n, v in restored.items():
                        ckpt_scope.set_var(n, v)
                    self._check_state_placement = True
                    extras = mgr.load_extras(start_step)
                    if "executor_rng_key" in extras:
                        # resume the rng STREAM, not just the params —
                        # dropout continues exactly where the
                        # interrupted run left off
                        self._root_key = jnp.asarray(
                            extras["executor_rng_key"])
                    if mon.is_enabled():
                        mon.counter("resilience.auto_resume").add(1)
                        mon.counter("resilience.batches_skipped").add(
                            start_step)
        if start_step:
            import itertools

            # skip already-consumed RAW batches (before prepare(): no
            # wasted sparse pulls), preserving the data cursor of the
            # interrupted run
            dataset = itertools.islice(iter(dataset), start_step, None)

        # sparse_config: one entry dict, a list of them, or (when None)
        # whatever the DistributeTranspiler attached to the program
        sp = sparse_config
        if sp is None:
            sp = getattr(program, "_ps_sparse_config", None) \
                or getattr(real_prog, "_ps_sparse_config", None)
        entries = list(sp) if isinstance(sp, (list, tuple)) \
            else ([sp] if sp else [])
        # tolerate partial/dense configs: no table -> dense path
        entries = [e for e in entries if e and e.get("table") is not None]
        if keep_replay and entries and _sparse_push:
            raise ValueError(
                "anomaly-guard rollback cannot be combined with sparse "
                "gradient push: pushed rows can't be unwound by a "
                "checkpoint restore (use policy='skip_step' or drop the "
                "sparse tables)")
        for e in entries:
            # Communicator wraps a table: pull reads through, push goes
            # via the communicator's mode (sync/async/half_async/geo)
            e["_pull"] = getattr(e["table"], "table", e["table"])
            e["_grad"] = e["emb_var"] + "@GRAD"

        if prefetch is None:
            # auto: overlap only where concurrent pull/push is already
            # the table's contract — async/half_async Communicators push
            # from their own background thread (locked shards). geo
            # flushes on the CALLING thread, and plain SparseEmbedding
            # is strictly synchronous: both stay un-overlapped.
            # Read-only draining (infer_from_dataset) never pushes, so
            # it has no ordering constraint at all.
            def _is_async(e):
                mode = getattr(e["table"], "mode", None)
                return mode in ("async", "half_async")

            prefetch = (not _sparse_push
                        or all(_is_async(e) for e in entries))

        def prepare(batch):
            # latency injection for the input pipeline (the goodput
            # chaos bench stalls batch preparation here): armed-gated,
            # so the unarmed path pays one None check
            if res.faultinject.is_armed():
                res.faultinject.stall_point("reader.prepare")
            feed = {k: v for k, v in batch.items()
                    if blk._find_var_recursive(k) is not None}
            fl = list(fetch_names)
            batch_ids = {}
            for e in entries:
                ids = np.asarray(batch[e["ids_var"]])
                batch_ids[e["emb_var"]] = ids
                feed[e["emb_var"]] = e["_pull"].pull(ids)
                if _sparse_push:
                    fl.append(e["_grad"])
            return feed, fl, batch_ids

        if prefetch:
            # producer thread keeps one prepared batch in flight: batch
            # N+1's iteration + embedding pull overlap batch N's step
            import queue as _queue
            import threading as _threading

            q = _queue.Queue(maxsize=2)
            stop = _threading.Event()
            _END, _ERR = object(), object()

            def _offer(item):
                # bounded put that gives up when the consumer is gone,
                # so a raising train loop can't strand this thread
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        return True
                    except _queue.Full:
                        continue
                return False

            def produce():
                try:
                    for b in dataset:
                        if not _offer(prepare(b)):
                            return
                    _offer(_END)
                except BaseException as exc:   # propagate to consumer
                    _offer((_ERR, exc))

            t = _threading.Thread(target=produce, daemon=True)
            t.start()

            def _host_batches():
                try:
                    while True:
                        item = q.get()
                        if item is _END:
                            return
                        if isinstance(item, tuple) and item[0] is _ERR:
                            raise item[1]
                        yield item
                finally:
                    stop.set()        # unblock + retire the producer

            def prepared_batches():
                gen = _host_batches()
                if not entries and not keep_replay and \
                        not getattr(program, "_is_data_parallel", False):
                    # dense single-device path: double-buffered DEVICE
                    # prefetch on top of the host producer thread — feed
                    # arrays are device_put while the previous step runs
                    # (buffered_reader.cc's device double buffer).  The
                    # sparse path keeps host batches: ids must stay host
                    # arrays for the gradient push, and its overlap win
                    # (the TCP pull) already lives on the producer
                    # thread.  The data-parallel path also keeps host
                    # batches: device_put would land the FULL batch on
                    # device 0 for jit to reshard (an extra d2d hop +
                    # device-0 memory spike), whereas the numpy feed
                    # lets jit place each dp shard directly.  The
                    # rollback-replay path also keeps host batches: the
                    # replay buffer retains every feed since the last
                    # save, and pinning those as DEVICE arrays would
                    # burn HBM proportional to the save interval (host
                    # RAM is the right place for a recovery window).
                    from ..reader import device_prefetch

                    gen = device_prefetch(gen, size=2)
                return gen
        else:
            def prepared_batches():
                for b in dataset:
                    yield prepare(b)

        # Steady-state no-sync contract: fetches come back as DEVICE
        # arrays (return_numpy=False) and are only materialized on host
        # at print_period boundaries and for the final batch, so jax's
        # async dispatch pipelines the host several steps ahead of the
        # device (composing with the producer thread + device_prefetch
        # double buffer above).  The sparse push is the one per-step
        # exception: the gradient rows must reach the host to be pushed.
        if keep_replay and mgr.latest_step() is None:
            # rollback needs a restore point covering the WHOLE loop:
            # without this, an anomaly before the first interval save
            # has nowhere to roll back to.  (After the sparse-config
            # validation — a config error must win over a save.)
            initial = _ckpt_state()
            if initial:
                mgr.save(initial, start_step, force=True,
                         extras=_ckpt_extras())
        last = None
        step_i = start_step
        replay = []          # [(step_no, feed, fl)] since the last save

        def _elastic_rethrow(e):
            # a preemption-shaped dispatch failure (dead peer, lost
            # heartbeat, reset transport) under the coordinator is a
            # TOPOLOGY event, not a retryable blip: the state of this
            # step may be consumed (donated buffers), so the catcher
            # reshards from the newest complete checkpoint and replays
            # its cursor — no force-save here
            if elastic is None:
                return
            ev = elastic.on_dispatch_error(e, step=step_i)
            if ev is None:
                return
            survivors = [m for m in elastic.members
                         if m not in ev["ranks"]]
            action = ("reshard_local"
                      if survivors == [elastic.rank] else "relaunch")
            from ..resilience.elastic import TopologyChanged

            raise TopologyChanged(step_i, ev, action) from e

        for feed, fl, batch_ids in _goodput_batches(prepared_batches()):
            if elastic is not None:
                with _gspan("elastic_transition"):
                    ev = elastic.step_boundary(step_i)
                if ev is not None:
                    kind = ev["kind"]
                    if kind == "self_leave" and ev.get("reason") == \
                            "drain":
                        # SIGUSR1 drain-and-leave: durable boundary
                        # state, leave intent already posted, exit
                        # cleanly and stay re-admittable
                        with _gspan("elastic_transition"):
                            elastic.force_save(_ckpt_state(), step_i,
                                               extras=_ckpt_extras())
                        if mon.is_enabled():
                            mon.counter(
                                "resilience.elastic_drain_exits").add(1)
                        break
                    if kind == "rank_join":
                        # grow force-saves the rendezvous checkpoint,
                        # commits the enlarged topology, and raises
                        # TopologyChanged(action="relaunch")
                        with _gspan("elastic_transition"):
                            elastic.grow(step_i, ev["ranks"],
                                         save_state=_ckpt_state(),
                                         extras=_ckpt_extras())
                    if kind in ("rank_leave", "rank_death", "evict"):
                        # survivors force-save at THIS boundary; the
                        # caller drives the shrink (reshard in process
                        # or orchestrator relaunch) from the durable
                        # state — the loop's compiled world is stale
                        with _gspan("elastic_transition"):
                            elastic.force_save(_ckpt_state(), step_i,
                                               extras=_ckpt_extras())
                        survivors = [m for m in elastic.members
                                     if m not in ev["ranks"]]
                        action = ("reshard_local"
                                  if survivors == [elastic.rank]
                                  else "relaunch")
                        from ..resilience.elastic import TopologyChanged

                        raise TopologyChanged(step_i, ev, action)
                    # kind == "self_leave"/"preempt": fall through to
                    # the preemption block below, which force-saves,
                    # clears the flag, and exits
            if res.preemption_requested():
                # preemption-safe exit: force-checkpoint at this STEP
                # BOUNDARY (never mid-step) and leave the loop cleanly;
                # a re-launch with auto_resume=True continues here.
                # (Counted HERE, not in the signal handler — the
                # handler must stay async-signal-safe.)
                if mon.is_enabled():
                    mon.counter("resilience.preempt_requested").add(1)
                fr = _fr()
                if fr.enabled:
                    fr.note_event("preemption", step=step_i,
                                  checkpointed=mgr is not None)
                if mgr is None:
                    # stopping is still right, but a checkpoint-less
                    # loop can't consume the flag (an enclosing
                    # checkpointed loop might) — without this warning a
                    # process with NO such loop silently turns every
                    # later train_from_dataset into a 0-step no-op
                    import warnings

                    warnings.warn(
                        "preemption requested but this train_from_"
                        "dataset has no checkpoint= store; stopping "
                        "WITHOUT saving.  Pass checkpoint=<dir|"
                        "CheckpointManager> (with auto_resume=True to "
                        "continue on relaunch) to make this exit "
                        "durable; for a fleet leave that peers should "
                        "shrink around, install PreemptionHandler("
                        "drain_signal=signal.SIGUSR1) under an "
                        "ElasticCoordinator instead.  The flag stays "
                        "set for an enclosing checkpointed loop — call "
                        "resilience.clear_preemption() if none exists.",
                        RuntimeWarning, stacklevel=2)
                if mgr is not None:
                    if mgr.latest_step() != step_i:
                        # already durable at this exact boundary?  Then
                        # do NOT rewrite it: save_checkpoint rmtree's
                        # the existing dir first, and a SIGKILL during
                        # the rewrite — the grace window running out,
                        # the very scenario this path serves — would
                        # lose the only fresh restore point
                        mgr.save(_ckpt_state(), step_i, force=True,
                                 extras=_ckpt_extras(),
                                 topology=(elastic.topology()
                                           if elastic is not None
                                           else None))
                    if mon.is_enabled():
                        mon.counter("resilience.preempt_checkpoint").add(1)
                    # HANDLED (durable checkpoint taken): leaving the
                    # flag up would make every later train_from_dataset
                    # in this process train zero steps (notebook
                    # re-runs, per-epoch loops).  A checkpoint-LESS
                    # drain (eval pass, ad-hoc loop) must NOT clear it:
                    # the enclosing training loop still has to see the
                    # request and take the real force-checkpoint.
                    res.clear_preemption()
                break
            if keep_replay:
                # run with data-cursor replay: a RollbackPerformed from
                # the guard restored checkpoint step S into the scope;
                # re-run the buffered batches S+1..current in order
                # (the failing batch included — injected faults are
                # one-shot; a persistent anomaly escalates via the
                # guard's max_rollbacks)
                pending = [(step_i + 1, feed, fl)]
                while pending:
                    sno, f, flx = pending.pop(0)
                    try:
                        out = self.run(program, feed=f, fetch_list=flx,
                                       scope=scope, return_numpy=False,
                                       _train_loop=True)
                    except res.RollbackPerformed as rb:
                        redo = [it for it in replay if it[0] > rb.step]
                        replay = [it for it in replay
                                  if it[0] <= rb.step]
                        pending = redo + [(sno, f, flx)] + pending
                        continue
                    except Exception as e:
                        _elastic_rethrow(e)
                        raise
                    replay.append((sno, f, flx))
            else:
                try:
                    out = self.run(program, feed=feed, fetch_list=fl,
                                   scope=scope, return_numpy=False,
                                   _train_loop=True)
                except Exception as e:
                    _elastic_rethrow(e)
                    raise
            if entries and _sparse_push:
                n = len(entries)
                if guard is not None and guard.last_skipped:
                    # a skipped step commits NOTHING — that contract
                    # covers the sparse half too: these gradient rows
                    # are the NaNs the guard just refused to apply
                    out = out[:-n]
                else:
                    # per-step sparse sync point: awaiting the gradient
                    # rows is awaiting the step's device execution
                    with _gspan("productive_step"):
                        grads = _materialize(out[-n:])
                    for e, g in zip(entries, grads):
                        e["table"].push(batch_ids[e["emb_var"]], g)
                    out = out[:-n]
            last = out
            step_i += 1
            gled = _gp().active()
            if gled is not None:
                gled.note_step()
            if mgr is not None and mgr.should_save(step_i):
                # interval-gated BEFORE building the state dict: the
                # 999 gated-off steps of a 1000-step interval must not
                # pay per-var scope lookups or the rng-key host copy
                # (the loop's no-sync contract).  Under a coordinator,
                # every save carries the committed topology stamp —
                # restore_resharded's provenance must name the world
                # that WROTE the checkpoint, whichever save path won
                # the boundary.
                saved = mgr.save(_ckpt_state(), step_i,
                                 extras=_ckpt_extras(),
                                 topology=(elastic.topology()
                                           if elastic is not None
                                           else None))
                if saved is not None:
                    # everything up to step_i is durable: the replay
                    # window restarts here
                    replay = [it for it in replay if it[0] > step_i]
            if (debug or fetch_info) and fetch_names \
                    and step_i % print_period == 0:
                # print-period sync: draining the async pipeline here
                # waits on the steps it had in flight
                with _gspan("productive_step"):
                    vals = _materialize(out)
                msg = ", ".join(
                    f"{info}={v.mean():.6f}"
                    for info, v in zip(fetch_info, vals))
                print(f"[train_from_dataset] step {step_i}: {msg}")
        if mon.is_enabled():
            # loop-end fleet record (ISSUE 10): the rolling skew table
            # rides the telemetry stream once per loop, so a JSONL
            # report (or a post-mortem) names the straggler without
            # asking the live process
            mon.record_fleet_skew(
                key=getattr(program, "_telemetry_label", None))
        if not fetch_names:
            return None
        if last is None:
            return None
        # final sync: the async pipeline's remaining in-flight steps
        # complete here
        with _gspan("productive_step"):
            return _materialize(last)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           prefetch=None):
        """executor.py:1130 parity — same drain loop but READ-ONLY on the
        sparse tables: embedding rows are still pulled to feed the
        program, gradients are neither fetched nor pushed (so prefetch
        auto-enables: there is no pull/push ordering constraint)."""
        return self.train_from_dataset(
            program=program, dataset=dataset, scope=scope, thread=thread,
            debug=debug, fetch_list=fetch_list, fetch_info=fetch_info,
            print_period=print_period, _sparse_push=False,
            prefetch=prefetch)

    # ------------------------------------------------------------------
    @staticmethod
    def _live_ops(program, fetch_names):
        """Run-time dead-op elimination (the reference achieves this via
        feed/fetch-targeted pruning in executor.py:236/274 + _prune): keep
        ops that contribute to a fetch or to a persistable-variable update
        (optimizer steps, batch-norm stats).  Programs with backward
        sections run unpruned — everything feeds the update."""
        ops = list(program.global_block().ops)
        if program.backward_sections and not program._is_test:
            return ops
        persist = {v.name for v in program.list_vars() if v.persistable}
        needed = set(fetch_names)
        keep = [False] * len(ops)
        for i in range(len(ops) - 1, -1, -1):
            outs = set(ops[i].output_names())
            # side-effecting ops (runtime printing) survive regardless of
            # consumers — their output IS the side effect
            if outs & needed or outs & persist \
                    or ops[i].type in _SIDE_EFFECT_OPS:
                keep[i] = True
                needed |= set(ops[i].input_names())
        return [op for i, op in enumerate(ops) if keep[i]]

    def _build(self, program, fetch_names, persist_names, dp_mesh=None,
               precision=None, feed_casts=None, telemetry_key=None,
               guard_on=False, spmd_plan=None):
        ops = self._live_ops(program, fetch_names)
        sections = [] if program._is_test else list(program.backward_sections)
        if telemetry_key is None:
            # stable, readable ledger key: program identity + mutation
            # version + what it fetches (CompiledProgram.with_telemetry
            # overrides with a human-chosen label)
            telemetry_key = "prog%x:v%d" % (id(program), program._version)
        return self._build_step(ops, sections, fetch_names, persist_names,
                                dp_mesh, precision=precision,
                                feed_casts=feed_casts,
                                telemetry_key=telemetry_key,
                                guard_on=guard_on, spmd_plan=spmd_plan)

    def _build_step(self, ops, sections, fetch_names, persist_names,
                    dp_mesh, precision=None, feed_casts=None,
                    telemetry_key="program", guard_on=False,
                    spmd_plan=None):
        dp = dp_mesh is not None
        spmd = spmd_plan is not None
        # var maps for the mem-profile's variable-class attribution:
        # which entry arguments are optimizer-updated parameters vs
        # other persistable state (stats buffers, optimizer moments)
        var_info = {
            "params": frozenset(n for bs in sections
                                for n in bs.param_names),
            "persist": frozenset(persist_names),
        }

        pins = None
        state_pins = None
        model_axes = frozenset()
        if spmd:
            from jax.sharding import NamedSharding

            # inside the shard_map body the dp axis is manual, so the
            # lowered constraints name only the GSPMD auto (model)
            # axes — body_spec strips the data axis
            model_axes = frozenset(a for a in dp_mesh.axis_names
                                   if a != "dp")

            def _ns(spec):
                return NamedSharding(
                    dp_mesh, spmd_plan.body_spec(spec).to_jax())

            # activation pins at the propagator-marked edges, keyed by
            # var name (the producing op pins its output right after
            # emission — see interpret)
            pins = {name: _ns(spec)
                    for _i, name, spec in spmd_plan.constraints}
            # output-state pins: the donated state's layout is pinned
            # to its input placement, or XLA's own inference would
            # re-layout the donated buffers and retrace every step
            # (the distributed.sharded make_sharded_train_step lesson)
            state_pins = {n: _ns(s)
                          for n, s in spmd_plan.state_specs.items()}

        def make_step(dp, with_pins=True):
            return self._make_step_fn(ops, sections, fetch_names,
                                      persist_names, dp,
                                      feed_casts=feed_casts,
                                      guard_on=guard_on,
                                      telemetry_key=telemetry_key,
                                      pins=pins if with_pins else None,
                                      state_pins=(state_pins
                                                  if with_pins else None),
                                      spmd=spmd)
        step = make_step(dp)

        if not dp:
            # instrument_jit routes each new input signature's compile
            # through the monitor's AOT path (timed, cost/memory
            # analyzed) while telemetry is on; a pass-through implicit
            # jit call otherwise
            return _mon().instrument_jit(
                jax.jit(apply_precision_policy(step, precision),
                        donate_argnums=(0,)), key=telemetry_key,
                var_info=var_info)

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def dp_step(state, feeds, key):
            # per-device rng diversity (dropout) while state stays in
            # sync.  GSPMD tier: the fold already happened on host (a
            # manual-axis axis_index would lower to the PartitionId op
            # partial-manual modules reject) — the [dp, 2] key stack
            # arrives sharded over dp, each shard takes its row.
            if spmd:
                key = key[0]
            else:
                key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            return step(state, feeds, key)

        # for shape-only evaluation: no pins (they don't change shapes)
        plain_step = make_step(False, with_pins=False)
        memo = {}

        def compiled(state, feeds, key):
            # rank-0 fetches are replicated (pmean'd reductions); rank>=1
            # fetches concatenate over dp like ParallelExecutor's fetch
            # merge (pybind fetch path). Ranks from a shape-only eval.
            sig = tuple(sorted(
                (n, a.shape, str(a.dtype)) for n, a in feeds.items()))
            fn = memo.get(sig)
            if fn is None:
                from ..monitor import fleet as _fleet_names

                # the skew probe's reserved feeds never enter the
                # program; its wait vector rides as one extra fetch
                # BEYOND the shape-evaluated ones (replicated by the
                # all_gather, so out-spec P() with no fetch-sync pmean)
                has_fleet = _fleet_names.FLEET_TS_SEC in feeds
                # feeds split over the data axis only: on a {dp,mp}
                # rule mesh each mp shard sees the whole dp-local batch
                ndev = (int(dp_mesh.shape["dp"]) if spmd
                        else dp_mesh.devices.size)
                local_feeds = {
                    n: jax.ShapeDtypeStruct(
                        (a.shape[0] // ndev,) + a.shape[1:], a.dtype)
                    for n, a in feeds.items()
                    if not n.startswith("__fleet_")
                }
                avals = jax.eval_shape(
                    plain_step,
                    {n: jax.ShapeDtypeStruct(np.shape(v),
                                             jnp.asarray(v).dtype)
                     for n, v in state.items()},
                    local_feeds, jax.ShapeDtypeStruct((2,), np.uint32))
                fetch_ranks = [len(f.shape) for f in avals[1]]

                def dp_step_shaped(state, feeds, key):
                    new_state, fetches = dp_step(state, feeds, key)
                    skew = None
                    if has_fleet:
                        skew = fetches[-1]
                        fetches = fetches[:-1]
                    with jax.named_scope("update/dp_fetch_sync_0"):
                        fetches = [f if r >= 1
                                   else jax.lax.pmean(f, "dp")
                                   for f, r in zip(fetches, fetch_ranks)]
                    if skew is not None:
                        fetches = fetches + [skew]
                    return new_state, fetches

                out_fetch_specs = [
                    P("dp") if r >= 1 else P() for r in fetch_ranks]
                if has_fleet:
                    # GSPMD tier: the probe returns its LOCAL wait row
                    # (no in-body AllGather — XLA's propagation drops
                    # it in partial-manual modules) and the out-spec
                    # boundary concatenates the [dp] vector instead
                    out_fetch_specs = out_fetch_specs + [
                        P("dp") if spmd else P()]
                # GSPMD tier: only dp is a manual axis — the model axes
                # stay automatic, so XLA propagates the state placements
                # + body pins and inserts the mp collectives itself
                # while the bucketed grad sync / skew probe machinery
                # runs on dp as-is
                sm_kw = {"axis_names": frozenset({"dp"})} if spmd else {}
                fn = _mon().instrument_jit(
                    jax.jit(apply_precision_policy(shard_map(
                        dp_step_shaped, mesh=dp_mesh,
                        in_specs=(P(), P("dp"),
                                  P("dp") if spmd else P()),
                        out_specs=(P(), out_fetch_specs),
                        check_vma=False, **sm_kw), precision),
                        donate_argnums=(0,)),
                    key=telemetry_key + ":dp", var_info=var_info)
                memo[sig] = fn
            return fn(state, feeds, key)

        return compiled

    def _make_step_fn(self, ops, sections, fetch_names, persist_names, dp,
                      feed_casts=None, guard_on=False,
                      telemetry_key=None, pins=None, state_pins=None,
                      spmd=False):
        # optimizer-updated params: identical across dp replicas by
        # construction, so exempt from the SyncBN-style stats averaging
        param_names = set()
        for bs in sections:
            param_names.update(bs.param_names)
        feed_casts = feed_casts or {}
        # ProgramDesc provenance: every op's kernel emission is wrapped
        # in jax.named_scope at trace time (see run_op), so the lowered
        # HLO carries per-op attribution metadata at zero runtime cost
        scopes = {id(op): name
                  for op, name in zip(ops, op_scopes(ops, sections))}
        if guard_on:
            from ..resilience.guard import all_finite as _all_finite_tree

        def step(state, feeds, key):
            env = {}
            env.update(state)
            finite = jnp.asarray(True) if guard_on else None
            # fleet skew probe (ISSUE 10): the reserved timestamp feeds
            # never enter the program env — they feed the barrier-wait
            # collective emitted in the dp_grad_sync scope below
            fleet_ts = None
            if dp and "__fleet_ts_sec__" in feeds:
                fleet_ts = (feeds["__fleet_ts_sec__"],
                            feeds["__fleet_ts_usec__"])
            skew = None
            # device-resident feeds whose dtype mismatches the declared
            # var dtype are cast HERE, inside the compiled step — the
            # cast fuses into the step instead of costing the dispatch
            # path a separate per-call device computation
            for n, v in feeds.items():
                if n.startswith("__fleet_"):
                    continue
                env[n] = v.astype(feed_casts[n]) if n in feed_casts else v
            const_env = {}
            rng_box = _RngBox(key)
            pos = 0
            for sec_i, bs in enumerate(sections):
                seg = ops[pos:bs.pos]
                train_params = {
                    n: env[n] for n in bs.param_names if n in env
                }
                chunks = _checkpoint_chunks(seg, bs.checkpoint_names)

                def fwd(ps, _env=dict(env), _chunks=chunks,
                        _loss=bs.loss_name, _key=rng_box.key):
                    e = dict(_env)
                    e.update(ps)
                    box_key = _key
                    for chunk, remat in _chunks:
                        if remat:
                            # recompute segment (RecomputeOptimizer /
                            # backward.py:623 parity) via jax.checkpoint
                            def run_chunk(e_in, k, _c=chunk):
                                e2 = dict(e_in)
                                b = _RngBox(k)
                                interpret(_c, e2, b, const_env, scopes,
                                          allow_sampling=False,
                                          pins=pins)
                                return e2, b.key

                            e, box_key = jax.checkpoint(run_chunk)(e, box_key)
                        else:
                            b = _RngBox(box_key)
                            interpret(chunk, e, b, const_env, scopes,
                                      allow_sampling=False, pins=pins)
                            box_key = b.key
                    loss = e[_loss]
                    return jnp.sum(loss), (e, box_key)

                (loss_val, (env, new_key)), grads = jax.value_and_grad(
                    fwd, has_aux=True
                )(train_params)
                rng_box = _RngBox(new_key)
                if guard_on:
                    # anomaly guard: ONE fused reduction per section over
                    # the loss and the raw (pre-sync, still scaled under
                    # AMP — exactly where update_loss_scaling samples)
                    # gradients; folded into the compiled step so the
                    # check costs no extra dispatch
                    finite = finite & jnp.isfinite(loss_val) \
                        & _all_finite_tree(grads)
                # DP gradient sync — the one collective the reference
                # inserts as allreduce op-handles
                # (multi_devices_graph_pass.cc:446), coalesced here by
                # transpiler.collective.sync_gradients into flattened
                # fixed-capacity buckets (FLAGS_dp_bucket_bytes; the
                # fuse_all_reduce_op_pass analogue — bitwise-identical
                # to per-gradient psums).  Framework-inserted (no
                # ProgramDesc op to blame), so it keeps its OWN
                # attribution scope: on a dp mesh the allreduce is real
                # device time and must not land in the unattributed
                # residual.
                with jax.named_scope(f"fwd{sec_i}/dp_grad_sync_{sec_i}"):
                    if dp:
                        from ..transpiler import collective as _coll

                        # keyed per program so the pass ledger keeps
                        # one bucketing record PER dp program instead
                        # of newest-wins under one shared key
                        synced = _coll.sync_gradients(
                            grads, "dp", key=telemetry_key)
                        if fleet_ts is not None and skew is None:
                            # the straggler probe rides the SAME scope
                            # as the bucketed grad collectives: one
                            # extra scalar pair per step, attributed to
                            # dp_grad_sync like the psums it measures
                            skew = _coll.emit_skew_probe(
                                fleet_ts[0], fleet_ts[1], "dp",
                                gather=not spmd)
                    else:
                        synced = grads
                    for n, g in synced.items():
                        env[n + "@GRAD"] = g
                pos = bs.pos
            interpret(ops[pos:], env, rng_box, const_env, scopes,
                      allow_sampling=False, pins=pins)
            fetches = [env[n] for n in fetch_names]
            new_state = {n: env[n] for n in persist_names if n in env}
            if dp:
                # params were updated identically (grads pmean'd) and need
                # no second collective; non-param float stats buffers
                # (batch-norm running stats) diverge with the local shard
                # -> average, SyncBN-style. Integer state (counters) is
                # identical across devices and must NOT go through pmean
                # (true division would float-ify it).  Scoped like the
                # grad sync: framework collective, own attribution row.
                with jax.named_scope("update/dp_state_sync_0"):
                    new_state = {
                        n: (jax.lax.pmean(v, "dp")
                            if n not in param_names and jnp.issubdtype(
                                jnp.asarray(v).dtype, jnp.floating)
                            else v)
                        for n, v in new_state.items()}
            if guard_on:
                with jax.named_scope("update/guard_check_0"):
                    # the flag travels as float32 so the dp fetch pmean
                    # averages it: ANY shard's anomaly pulls it below 1.0
                    flag = finite.astype(jnp.float32)
                    if dp:
                        flag = jax.lax.pmean(flag, "dp")
                    ok = flag >= 1.0
                    # an anomalous step commits NOTHING: select the old
                    # state on device (same contract as the AMP scaler's
                    # skip-on-overflow).  XLA copies where donation would
                    # alias — correctness first, the guard is opt-in.
                    new_state = {
                        n: (jnp.where(ok, jnp.asarray(v),
                                      jnp.asarray(state[n]))
                           if n in state else v)
                        for n, v in new_state.items()}
                fetches = fetches + [flag]
            if state_pins:
                # pin each donated state output to its INPUT layout:
                # without this XLA is free to infer a different output
                # sharding for the updated state, which both breaks
                # donation aliasing and retraces the step next call
                # with the drifted placement
                with jax.named_scope("update/spmd_state_pin_0"):
                    new_state = {
                        n: (jax.lax.with_sharding_constraint(
                                v, state_pins[n])
                            if n in state_pins else v)
                        for n, v in new_state.items()}
            if fleet_ts is not None:
                if skew is None:
                    # no backward section carried the probe (eval / dp
                    # inference program): emit it with the state-sync
                    # framework collectives instead
                    from ..transpiler import collective as _coll

                    with jax.named_scope("update/dp_grad_sync_fleet"):
                        skew = _coll.emit_skew_probe(
                            fleet_ts[0], fleet_ts[1], "dp",
                            gather=not spmd)
                # the wait vector is the VERY last fetch — the executor
                # pops it before the guard flag's own pop
                fetches = fetches + [skew]
            return new_state, fetches

        return step

    # ------------------------------------------------------------------
    def _run_eager(self, program, feed_arrays, fetch_names, scope, key,
                   return_numpy):
        """Op-by-op interpretation without jit (FLAGS_eager_executor), with
        per-op NaN/Inf checking when FLAGS_check_nan_inf is set (parity:
        operator.cc:1032 + nan_inf_utils_detail.cc)."""
        check = flags.flag("check_nan_inf")
        env = {}
        for n, v in scope.vars.items():
            if v is not None:
                env[n] = v
        env.update(feed_arrays)
        rng_box = _RngBox(key)
        ops = self._live_ops(program, fetch_names)
        sections = [] if program._is_test else list(program.backward_sections)
        scopes = {id(op): name
                  for op, name in zip(ops, op_scopes(ops, sections))}
        pos = 0
        persist = {v.name for v in program.list_vars() if v.persistable}

        def run_seg(seg):
            if not check:
                # the sampling-aware loop: per-op timing when a
                # monitor.op_profile sampler is active
                interpret(seg, env, rng_box, None, scopes)
                return
            for op in seg:
                run_op(op, env, rng_box, None, scopes.get(id(op)))
                for slot, names in op.outputs.items():
                    for n in names:
                        if n in env and jnp.issubdtype(
                            jnp.asarray(env[n]).dtype, jnp.floating
                        ):
                            if not bool(jnp.all(jnp.isfinite(env[n]))):
                                raise FloatingPointError(
                                    f"op '{op.type}' output '{n}' "
                                    f"contains NaN/Inf"
                                )

        for bs in sections:
            seg = ops[pos:bs.pos]
            train_params = {n: env[n] for n in bs.param_names if n in env}

            def fwd(ps, _env=dict(env), _seg=seg, _key=rng_box.key):
                e = dict(_env)
                e.update(ps)
                box = _RngBox(_key)
                interpret(_seg, e, box, None, scopes)
                return jnp.sum(e[bs.loss_name]), (e, box.key)

            (loss_val, (env, new_key)), grads = jax.value_and_grad(
                fwd, has_aux=True
            )(train_params)
            rng_box = _RngBox(new_key)
            if check:
                for n, g in grads.items():
                    if not bool(jnp.all(jnp.isfinite(g))):
                        raise FloatingPointError(f"gradient of '{n}' has NaN/Inf")
            for n, g in grads.items():
                env[n + "@GRAD"] = g
            pos = bs.pos
        run_seg(ops[pos:])

        for n in persist:
            if n in env:
                scope.set_var(n, env[n])
        fetches = [env[n] for n in fetch_names]
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches
