#!/usr/bin/env python3
"""``MoE``'s row sum kernel (``mxnet_tpu/ops/row_sum_kernels.py``) against
XLA's scatter-add on the attached chip, at a benchmark cell's layer shape and
under a router that collapses part of the way onto the held experts: the
routing no cell of the benchmark sends and a training run can reach.

    chiprun -- python tools/row_sum_repro.py --shape mellum2 --collapse 0.3 \
        --do sums --round 1

``--collapse C``: the first ``C`` of the tokens choose the held experts
alone (their runs are whole blocks long, start off a multiple of 16 rows and
are cut by the rounds), the rest route near balance. ``--do sums``: the
weighted and the unweighted sum of round ``--round`` alone, its ``runs``
clipped on the host as ``_held_round`` clips them. ``--do forward`` /
``layer``: ``_moe`` / ``jax.grad`` of it, every round, also against the
layer with no kernel at all (``ragged_dot`` masked on both sides: since PR 64
the grouped matmuls own a round's dead rows and nothing masks them). One run
a process: a kernel that halts the core takes its process with it, so a
caller that wants several runs starts several (``--do all`` does, each a
child, and goes on after one that fails). Prints one JSON line a run; exit 1
where a run failed or disagreed. On the CPU (a dry run) the kernel runs in
Pallas's interpreter at ``--shape tiny``.
"""
import argparse
import json
import os
import subprocess
import sys

SHAPES = {  # tokens, hidden, experts, held, top_k, expert width
    "mellum2": (16384, 2304, 64, 8, 8, 896),
    "sdar": (16384, 2048, 128, 16, 8, 768),
    "keye": (16384, 2048, 128, 8, 8, 768),
    "zaya": (8192, 2048, 16, 8, 1, 2048),
    "kanana2": (8192, 2048, 128, 8, 6, 768),
    "qwen3next": (8192, 2048, 512, 16, 10, 512),
    "trinity": (4096, 2048, 128, 8, 8, 1024),
    "kimi": (4096, 2304, 256, 8, 8, 1024),
    "tiny": (512, 128, 16, 4, 2, 128),
}


def parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="mellum2", choices=sorted(SHAPES))
    ap.add_argument("--collapse", type=float, default=0.3)
    ap.add_argument("--do", default="all",
                    choices=["sums", "forward", "layer", "all"])
    ap.add_argument("--round", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


def children(a):
    """Every run of ``--do all``, each in a process of its own."""
    import numpy as np
    n, _, e, held, k, _ = SHAPES[a.shape]
    rounds = 1 + int(a.collapse > 0) * 3   # asked for; dead ones say so
    runs = [["--do", "sums", "--round", str(r)] for r in range(rounds)]
    runs += [["--do", "forward"], ["--do", "layer"]]
    bad = 0
    for extra in runs:
        cmd = [sys.executable, os.path.abspath(__file__), "--shape", a.shape,
               "--collapse", str(a.collapse), "--seed", str(a.seed)] + extra
        done = subprocess.run(cmd, capture_output=True, text=True)
        lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
        if done.returncode or not lines:
            bad += 1
            tail = (done.stderr or done.stdout).strip().splitlines()[-12:]
            print(json.dumps({"run": " ".join(extra), "rc": done.returncode,
                              "stderr": [t[:400] for t in tail]}), flush=True)
        for line in lines:
            print(line, flush=True)
    return bad


def main():
    a = parse()
    if a.do == "all":
        sys.exit(1 if children(a) else 0)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.ops import defs_transformer as dt
    from mxnet_tpu.ops import pallas_support as ps
    from mxnet_tpu.ops import registry
    from mxnet_tpu.ops import row_sum_kernels as rs

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:
        rs.sum_rows = functools.partial(rs.sum_rows, interpret=True)
    vmem = ps.attached_vmem_bytes() or (128 << 20)
    n, h, e, held, k, f = SHAPES[a.shape]
    rows = dt.held_round_rows(n * k, held, e)
    rng = np.random.RandomState(a.seed)
    lg = rng.randn(n, e).astype("float32")
    lg[:int(a.collapse * n), :held] += 20.0
    expert = np.argsort(-lg, 1)[:, :k].astype(np.int32)
    flat = expert.reshape(-1)
    order = np.argsort(np.where(flat < held, flat, held), kind="stable")
    counts = np.bincount(flat, minlength=e)[:held]
    live = int(counts.sum())
    said = {"shape": a.shape, "collapse": a.collapse, "do": a.do,
            "rows": rows, "live": live, "device": jax.devices()[0].device_kind}

    def rel(got, want):
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        return float(np.max(np.abs(got - want))
                     / (np.max(np.abs(want)) + 1e-30))

    if a.do == "sums":
        plan = rs.kernel_plan("tpu", vmem, jnp.bfloat16, rows, n, h, held, k)
        first = a.round * rows
        said.update(round=a.round, plan=list(plan or ()))
        if plan is None or first >= live:
            print(json.dumps(dict(said, skipped="no plan" if plan is None
                                  else "a dead round")))
            return
        whole = np.pad(order, (0, -(-n * k // rows) * rows - n * k))
        tok = jnp.asarray(whole[first:first + rows] // k, jnp.int32)
        member = jnp.asarray((expert[:, :, None] == np.arange(held)).any(1))
        starts = jnp.asarray(np.cumsum(counts) - counts, jnp.int32)
        runs = jnp.clip(rs.block_runs(member, starts, plan.block) - first, 0,
                        rows)
        mask = (np.arange(rows) < live - first)[:, None]
        y = jnp.asarray(np.where(mask, rng.randn(rows, h), 0), jnp.bfloat16)
        w = jnp.asarray(rng.rand(rows) + 0.1, jnp.float32)
        want_w = jax.jit(lambda y, w, tok: jnp.zeros((n, h), jnp.float32)
                         .at[tok].add(y.astype(jnp.float32) * w[:, None]))(
                             y, w, tok)
        want_u = jax.jit(lambda y, tok: jnp.zeros((n, h), jnp.float32)
                         .at[tok].add(y.astype(jnp.float32)))(y, tok)
        got_u = jax.jit(lambda y, tok, runs: rs.sum_rows(
            y, tok, None, runs, n, jnp.bfloat16, plan))(y, tok, runs)
        said["unweighted"] = rel(got_u, want_u.astype(jnp.bfloat16))
        got_w = jax.jit(lambda y, w, tok, runs: rs.sum_rows(
            y, tok, w, runs, n, jnp.float32, plan))(y, w, tok, runs)
        said["weighted"] = rel(got_w, want_w)
        ok = said["unweighted"] < 1e-2 and said["weighted"] < 1e-5
    else:
        params = registry.get("MoE").parse_params(dict(
            num_experts=e, num_hidden=f, top_k=k, num_local_experts=held,
            router="graph", score_func="softmax", route_norm=True))
        mode = registry.OpMode(is_train=True, platform="tpu")
        x = jnp.asarray(rng.randn(n, h), jnp.bfloat16)
        head = jnp.asarray(rng.randn(n, h) * 0.01, jnp.float32)
        ws = tuple(jnp.asarray(rng.randn(*s) * 0.02, jnp.float32)
                   for s in [(held, h, f), (held, h, f), (held, f, h)])
        ins = (x, jnp.asarray(lg)) + ws

        def layer(*ins):
            return dt._moe(list(ins), params, mode)

        def loss(*ins):
            return jnp.sum(layer(*ins).astype(jnp.float32) * head)

        # the runs as the chip's own router gives them, against the host's
        def device_runs(logits):
            chose, _, counted = dt._router(logits, None, params)
            member = jnp.any(dt._chosen(chose.reshape(n, k), held), axis=1)
            return rs.block_runs(
                member, jnp.cumsum(counted[:held]) - counted[:held],
                rs._BLOCK)

        got_runs = np.asarray(jax.jit(device_runs)(jnp.asarray(lg)))
        host = (expert[:, :, None] == np.arange(held)).any(1)
        want_runs = np.asarray(rs.block_runs(
            jnp.asarray(host), jnp.asarray(np.cumsum(counts) - counts),
            rs._BLOCK))
        said["runs"] = {
            "equal_host": bool(np.array_equal(got_runs, want_runs)),
            "longest": int(np.diff(got_runs, axis=0).max()),
            "shortest": int(np.diff(got_runs, axis=0).min())}

        def program():
            """The compared program, traced anew under the rules as they
            stand (a function of its own a call: jax keeps traces by it)."""
            if a.do == "forward":
                return jax.jit(lambda *ins: layer(*ins))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

        plain = dt._row_sum_plan
        if on_cpu:   # what the chip's rule would say, in the interpreter
            dt._row_sum_plan = (
                lambda platform, dtype, rows, n, h, weights, top_k, v=None:
                rs.kernel_plan("tpu", vmem, dtype, rows, n, h,
                               weights[0].shape[0], top_k))
            dt._expert_plans = lambda *args, **kw: None
        got = jax.block_until_ready(program()(*ins))
        dt._row_sum_plan = lambda *args, **kw: None
        want = jax.block_until_ready(program()(*ins))
        # and with no kernel at all: ``ragged_dot`` masked on both sides
        # (what it leaves past its groups is not specified) and the
        # scatter-adds, against kernels that own the round's dead rows
        rule, dt._expert_plans = dt._expert_plans, lambda *args, **kw: None
        masked = jax.block_until_ready(program()(*ins))
        dt._row_sum_plan, dt._expert_plans = plain, rule
        said["rel_err"], said["rel_err_no_kernel"] = (
            [rel(p, q) for p, q in zip(jax.tree.leaves(got),
                                       jax.tree.leaves(side))]
            for side in (want, masked))
        said["finite"] = all(bool(np.isfinite(np.asarray(
            p, np.float32)).all()) for p in jax.tree.leaves(got))
        ok = said["finite"] and max(
            said["rel_err"] + said["rel_err_no_kernel"]) < 3e-2
    print(json.dumps(dict(said, ok=bool(ok))), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
