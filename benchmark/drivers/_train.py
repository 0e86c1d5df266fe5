"""What the two training drivers share: one ``fit`` call whose batch-end
callback drives warm-up, the fenced slices and the traced slice.

The program is entered only through ``Module.fit`` / ``BucketingModule.fit``
and read only through its outputs, its parameters, ``telemetry.snapshot()``
and jax's compile events. Fences and loss reads go straight to the jax
arrays, so they do not pass the program's counted ``asnumpy`` /
``wait_to_read``; a :class:`SyncLedger` proves it by counting around them.
"""

from __future__ import annotations

import contextlib
import math
import time

from benchmark.lib import gen
from benchmark.lib import harness as hx
from benchmark.lib import window as wn


def executor_of(mod):
    """The one SPMD executor behind a Module or the active bucket."""
    return getattr(mod, "_curr_module", None) or mod


def param_specs(shapes, rules, dtype="float32"):
    """[(name, shape, dtype, kind, scale, offset)] for ``gen.make_leaves``:
    ``rules(name, shape)`` -> (kind, scale, offset) is the configuration's."""
    return [(n, tuple(s), dtype) + tuple(rules(n, tuple(s)))
            for n, s in sorted(shapes.items())]


def make_params(run, arg_shapes, aux_shapes, placement=None):
    """(arg_params, aux_params) as NDArrays made on the device from the
    seed by one jitted call; ``placement`` is a sharding for all leaves."""
    mx, jax, builder = run["mx"], run["jax"], run["builder"]
    specs = param_specs(arg_shapes, builder.init_rule) + \
        param_specs(aux_shapes, builder.init_rule)
    leaves = gen.make_leaves(jax, run["args"].seed, specs,
                             out_shardings=placement)
    nd = mx.nd.NDArray
    return ({n: nd(leaves[n]) for n in arg_shapes},
            {n: nd(leaves[n]) for n in aux_shapes})


class StoppableIter:
    """Mixin state for the resident and the cycled iterators: one epoch
    that lasts until the window says stop, so that ``fit``'s epoch boundary
    (drain, metric read, parameter copy to the host) falls after it."""

    stop = False


def cross_entropy(jax):
    import jax.numpy as jnp

    @jax.jit
    def loss(prob, label):
        lab = label.reshape(-1).astype(jnp.int32)
        p = prob.reshape(lab.shape[0], -1).astype(jnp.float32)
        picked = jnp.take_along_axis(p, lab[:, None], axis=-1)[:, 0]
        return -jnp.mean(jnp.log(jnp.maximum(picked, 1e-30)))

    return loss


class Session:
    """One measured ``fit``. ``units_of(first, n)`` and ``resident`` (the
    loss must fall) come from the driver."""

    def __init__(self, run, mod, data_iter, *, slice_steps, cycle_steps,
                 min_slices, units_of, trace_steps, resident):
        self.run, self.mod, self.iter = run, mod, data_iter
        self.jax = run["jax"]
        self.tm = run["mx"].telemetry
        self.resident = resident
        self.ledger = hx.SyncLedger(self.tm)
        self.tracer = run["tracer"]
        self._loss_fn = cross_entropy(self.jax)
        self._label = None
        self._big = None
        self._step_span = None
        self.snap = {}
        self.gc_log = hx.GcLog()
        self._cpu_marks = []  # process CPU time at each window boundary
        self.win = wn.SliceWindow(
            seconds=run["seconds"], slice_steps=slice_steps,
            cycle_steps=cycle_steps, min_slices=min_slices,
            fence=self._fence,
            compile_events=run["clock"].mark, units_of=units_of,
            trace_steps=trace_steps if self.tracer.on else 0,
            trace_start=self.tracer.start, trace_stop=self.tracer.stop,
            read_loss=self._read_loss)

    # -- what the window calls ------------------------------------------
    def _outputs(self):
        return [o._data for o in self.mod.get_outputs()]

    def _fence(self):
        with self.ledger, self.tracer_span("bench.fence"):
            active = executor_of(self.mod)
            exe = active._exec_group.execs[0]
            if self._big is None:
                # the largest parameter is never in the small-parameter
                # pack, so reading it dispatches nothing
                self._big = max(active._param_names,
                                key=lambda n: exe.arg_dict[n].size)
            self.jax.block_until_ready(
                self._outputs() + [exe.arg_dict[self._big]._data])

    def _read_loss(self):
        with self.ledger:
            return float(self._loss_fn(self._outputs()[0], self._label._data))

    def tracer_span(self, name):
        if self.tracer.on and self.win.phase == wn.TRACE:
            return self.tracer.annotate(name)
        return contextlib.nullcontext()

    # -- fit's batch-end callback ----------------------------------------
    def callback(self, param):
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None
        win = self.win
        if win.phase == wn.DONE:
            return
        self._label = param.locals["data_batch"].label[0]
        before, slices = win.phase, len(win.slices)
        with self.tracer_span("bench.callback"):
            if win.first_cycle:
                self._read_loss()  # warms the loss program of this shape
            after = win.step()
        if (before, after) == (wn.WARMUP, wn.WINDOW) \
                or len(win.slices) > slices:
            # a stalled slice that burnt CPU was this process's own work
            self._cpu_marks.append(time.process_time())
        if before == wn.WARMUP and after == wn.WINDOW:
            self.snap["t0"] = self.tm.snapshot()
            self.snap["syncs0"] = self.ledger.total() - self.ledger.own
        if before == wn.WINDOW and after != wn.WINDOW:
            self.snap["t1"] = self.tm.snapshot()
            self.snap["syncs1"] = self.ledger.total() - self.ledger.own
        if after == wn.DONE:
            self.iter.stop = True
        elif after == wn.TRACE:
            # fit's own loop between two callbacks, for the gap attribution
            self._step_span = self.tracer.annotate("bench.fit")
            self._step_span.__enter__()

    # -- after fit returned ------------------------------------------------
    def finish(self, unit_size_check):
        """Fill ``run`` with what run.py and the layer readers need."""
        run, win = self.run, self.win
        if win.phase != wn.DONE:
            raise hx.BenchError(f"fit ended in phase {win.phase!r}: the "
                                "iterator ran out before the window")
        self.gc_log.close()
        s = wn.summarize(win.slices)
        in_window = run["clock"].events_between(win.t_warm, win.t_end)
        setup_s = win.t_warm - run["t_start"]
        finite = all(math.isfinite(v) for v in win.losses)
        fell = (not self.resident) or win.losses[-1] < win.losses[0]
        counted = s["units"] == unit_size_check(win.steps)
        hx.emit({"window": {
            "workload": run["cell"]["name"], "seed": run["args"].seed,
            "slices": len(win.slices), "steps_per_slice": win.slice_steps,
            "slice_rates": s["rates"], "whole_window_rate": s["mean_rate"],
            "median_slice_rate": s["median_rate"],
            "window_s": s["seconds"], "stalled_slices": s["stalled_slices"],
            "slice_cpu_s": [b - a for a, b in zip(self._cpu_marks,
                                                  self._cpu_marks[1:])],
            "gc_in_window": self.gc_log.between(win.t_warm, win.t_end),
            "compile_events_in_window": len(in_window),
            "compile_event_times_s": in_window,
            "fences": win.fences, "harness_counted_syncs": self.ledger.own,
            "warmup_cycles": win.cycles, "warmup_steps": win.warmup_steps,
            "setup_s": setup_s, "setup_compile_s": run["clock"].compile_s,
            "cache_hits": run["clock"].cache_hits,
            "cache_writes": run["clock"].cache_writes,
            "slice_losses": win.losses,
            "memory_stats": {k: v for k, v in (
                run["devices"][0].memory_stats() or {}).items()
                if isinstance(v, (int, float))},
            "checks": {"finite": finite, "loss_fell": fell,
                       "units_match_steps": counted}}})
        run["correct"] = finite and fell and counted
        run["attempted"] = win.steps + (win.trace_slice or (0, 0))[0]
        run["failed"] = 0 if finite else run["attempted"]
        run["setup_s"] = setup_s
        # before any reference check: the peak is the measured job's
        run["device_stamp"] = hx.device_stamp(run["devices"])
        run["summary"] = s
        run["obs"] = {
            "steps": win.steps, "window_s": s["seconds"],
            "units": s["units"], "rate": s["mean_rate"],
            "median_slice_rate": s["median_rate"],
            "tm0": self.snap["t0"], "tm1": self.snap["t1"],
            "program_syncs": self.snap["syncs1"] - self.snap["syncs0"],
            "setup_compile_s": run["clock"].compile_s,
            "flops_per_unit": run["builder"].train_flops_per_unit(
                run["config"]),
            "chips": run["cell"]["chips"],
            "peak_flops": run["peaks"]["bf16_tflops"] * 1e12,
            "trace_slice": win.trace_slice,
            "memory_peak_bytes": run["device_stamp"]["memory_peak_bytes"],
        }
        if self.tracer.on:
            run["obs"]["trace"] = self.tracer.reduce()
            hx.emit({"trace": {k: v for k, v in self.tracer.result.items()
                               if k not in ("device_ops", "idle_gaps")},
                     "traced_steps": win.trace_slice[0],
                     "traced_slice_host_s": win.trace_slice[1]})


def check_against_reference(run, name, got, want, tolerances):
    """Compare the program's first-step numbers with the plain reference's;
    prints both and folds the verdict into ``run['correct']``."""
    verdict = {}
    for key, tol in tolerances.items():
        rel = abs(got[key] - want[key]) / max(abs(want[key]), 1e-30)
        verdict[key] = {"program": got[key], "reference": want[key],
                        "rel_err": rel, "tolerance": tol, "ok": rel <= tol}
    ok = all(v["ok"] for v in verdict.values())
    hx.emit({"reference_check": name, "ok": ok, **verdict})
    run["correct"] = bool(run["correct"] and ok)
    return ok
