"""One run of one cell: set-up, the first three steps, the window, the
check against the reference, and the result line.

``run_cell`` does all of it on whatever backend jax has; ``bench/run.py``
looks for the chip first.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, gen, spec
from bench import trace as T

#: steps the reference follows; the window starts from the state after
#: them.
CHECKED_STEPS = 3
#: steps in flight in the window: the loop waits for the step dispatched
#: this many steps before the newest, so that a stall of the host shorter
#: than that many steps leaves the device busy.  Each step writes its
#: state and outputs into the buffers of the step before (donated), so the
#: depth costs no device memory.
IN_FLIGHT = 64
#: the longest window a traced run records.
TRACE_SECONDS = 5.0
PROFILE_DIR = spec.ROOT / ".cache" / "bench" / "profile"


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _annotate(on: bool):
    if on:
        return lambda name: jax.profiler.TraceAnnotation(name)
    return lambda name: contextlib.nullcontext()


def _advance(compiled, carry, batch):
    state, out, i = carry
    return compiled(state, batch, i, out)


def window(compiled, carry, pool, first: int, seconds: float,
           traced: bool = False):
    """Closed loop: dispatch steps back to back, with at most
    ``IN_FLIGHT`` unfinished, until ``seconds`` have passed, then wait for
    the last.  ``carry`` is ``(state, out, i)``, each step's output the next
    one's input, with ``i`` the step index on the device; the loop waits
    for a step's index, which is ready when the whole step is.  Returns
    ``(carry, steps, seconds)``."""
    note = _annotate(traced)
    inflight = collections.deque()
    n = 0
    t0 = time.perf_counter()
    while True:
        with note("bench:dispatch"):
            carry = _advance(compiled, carry,
                             pool[(first + n) % len(pool)])
        n += 1
        inflight.append(carry[2])
        if len(inflight) > IN_FLIGHT:
            with note("bench:wait"):
                inflight.popleft().block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with note("bench:wait"):
        jax.block_until_ready(carry)
    return carry, n, time.perf_counter() - t0


def _counters() -> dict:
    from repro.core import conv as C
    from repro.kernels import ops
    return {"dispatch": C.dispatch_events(),
            "plan_events": ops.plan_events(),
            "runtime_failures": C.runtime_failures(),
            "decisions": C.policy_decisions()}


def _reset_counters() -> None:
    from repro.core import conv as C
    from repro.kernels import ops
    C.reset_dispatch_events()
    ops.reset_plan_events()


def _engines(kind, decisions: list[dict]) -> list:
    """``(key, conv, pass, needed, engine)`` of every pass of one step,
    with the engine the program resolved it to (None where it recorded no
    decision for that geometry)."""
    engine = {(d["pass"], d["transpose"], tuple(d["dims"])): d["engine"]
              for d in decisions}
    return [(key, conv, p, needed, engine.get(key))
            for key, conv, p, needed in kind.passes()]


def _peak_bytes(devices) -> int:
    """The device memory peak of the fullest chip: the allocator's peak of
    buffers in use plus its peak reservation for programs' temporaries,
    which ``peak_bytes_in_use`` alone leaves out."""
    def peak(d):
        stats = d.memory_stats() or {}
        return (int(stats.get("peak_bytes_in_use", 0))
                + int(stats.get("peak_bytes_reserved", 0)))
    return max(peak(d) for d in devices)


def build(cell: spec.Cell, policy: str | None = None):
    return spec.step_kind(cell.traffic["step"]).build(
        cell.config, cell.traffic, policy or cell.traffic["policy"])


def draw(kind, cell: spec.Cell, seed: int):
    """The program's state from the seed's weights, and the batch pool."""
    state = kind.init_state(gen.draw(seed, gen.WEIGHTS, kind.weights_tree()))
    return state, gen.pool(seed, cell.traffic["pool"], kind.batch_tree())


def _counted(program):
    """The step as compiled: ``(state, batch, i, spent) -> (state, out,
    i + 1)``.  The next step index stays on the device: sending it from the
    host every step stalled a dispatch by 110 ms at times, in the
    host-to-device transfer.  ``spent`` is the step before's output, read
    by nothing: donated with the state, it gives this step's output its
    buffers, as a training loop that donates its state reuses them."""
    def step(state, batch, i, spent):
        del spent
        state, out = program(state, batch, i)
        return state, out, i + 1
    return step


def compile_step(kind, cell: spec.Cell, state, pool):
    """The compiled step, and ``(key, conv, pass, needed, engine)`` of each
    of its conv passes; the program's counters go to an earlier line."""
    with jax.default_matmul_precision(cell.config["precision"]):
        _reset_counters()
        program = jax.jit(kind.program)
        # Traced once: the compile below finds this trace in jit's cache.
        spent = jax.eval_shape(program, state, pool[0], np.int32(0))[1]
        compiled = jax.jit(_counted(program), donate_argnums=(0, 3),
                           keep_unused=True).lower(
            state, pool[0], np.int32(0), spent).compile()
    counters = _counters()
    passes = _engines(kind, counters.pop("decisions"))
    return compiled, passes, counters


def first_steps(compiled, state, pool):
    """The steps the reference follows, through the window's own call and
    feed: the carry after them (``window``), host copies of the states
    before step 1, after step 1 and after the last, and of each step's
    output.  ``state`` is donated to the first step."""
    out = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                       compiled.out_info[1])
    carry = (state, out, jax.device_put(np.int32(0)))
    states, outs = [_host(state)], []
    for n in range(CHECKED_STEPS):
        carry = _advance(compiled, carry, pool[n % len(pool)])
        outs.append(_host(carry[1]))
        if n == 0:
            states.append(_host(carry[0]))
    states.append(_host(carry[0]))
    return carry, states, outs


def reference(kind, cell: spec.Cell, seed: int, mode: str = "highest") \
        -> dict:
    """The reference's observation of the first steps, from the seed."""
    n_pool = cell.traffic["pool"]
    weights = gen.draw(seed, gen.WEIGHTS, kind.weights_tree())
    batches = gen.pool(seed, n_pool, kind.batch_tree())
    batches = [batches[i % n_pool] for i in range(CHECKED_STEPS)]
    return kind.reference(weights, batches, mode)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, policy: str | None = None, log=print) -> dict:
    """Everything of one run after the look for a chip; returns the result
    line as a dict."""
    kind = build(cell, policy)
    devices = jax.devices()[:cell.chips]

    # Set-up: weights and the batch pool from the seed, one compile, and
    # the first steps.
    state, pool = draw(kind, cell, seed)
    compiled, passes, counters = compile_step(kind, cell, state, pool)
    log("counters " + json.dumps(counters, sort_keys=True))
    log("engines " + json.dumps([[*k[:2], list(k[2]), e, needed]
                                 for k, _, _, needed, e in passes]))
    carry, states, outs = first_steps(compiled, state, pool)
    setup_s = time.perf_counter() - t_start

    if traced:
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(PROFILE_DIR))
        try:
            with jax.profiler.TraceAnnotation(T.WINDOW):
                carry, steps, window_s = window(
                    compiled, carry, pool, CHECKED_STEPS,
                    min(seconds, TRACE_SECONDS), traced=True)
        finally:
            jax.profiler.stop_trace()
    else:
        carry, steps, window_s = window(compiled, carry, pool,
                                        CHECKED_STEPS, seconds)
    peak = _peak_bytes(devices)
    del state, carry, pool, compiled
    gc.collect()

    # The reference, once the program's state is freed.
    nums = compare.numbers(kind.observe(states, outs),
                           reference(kind, cell, seed))
    correct, checks = compare.verdict(nums, cell.limits)

    result = {"correct": correct,
              "attempted": CHECKED_STEPS + steps,
              "failed": 0 if correct else CHECKED_STEPS}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if traced:
        events = T.read_profile(str(PROFILE_DIR))
        view = T.TraceView(events, cell.chips)
        ctx = {"view": view, "steps": steps, "passes": passes,
               "peak": spec.peaks(d0.device_kind)}
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=view.busy_s, window_s=view.window_s)
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": view.top_ops(), "idle_gaps": view.idle_gaps()})
    else:
        e2e = {"setup_s": setup_s, "step_ms": 1e3 * window_s / steps,
               "peak_hbm_mib": peak / 2 ** 20}
        result.update(metrics={m["name"]: {"value": e2e[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end}, device=device)
    result["checks"] = checks
    log("window " + json.dumps({"steps": steps, "seconds": window_s,
                                "numbers": nums}))
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result
