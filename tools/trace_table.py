#!/usr/bin/env python3
"""A profiler trace as tables: device time by operator and pass, by node,
by XLA program, and the device's idle gaps by the span the host was in.

    python tools/trace_table.py <.xplane.pb or profile dir> [--window SPAN]
        [--by operator|node|program|idle|inner --operator NAME]
        [--top N] [--json]

Prints what ``mxnet_tpu.profiler.device_table`` returns (its docstring says
what each number is). Times are milliseconds a step where the window holds
steps, else milliseconds. ``--window bench.traced_slice`` is the benchmark's
traced slice; ``--window fit.step`` one iteration of ``Module.fit``.
``--by inner --operator MoE`` splits one operator's time by what follows
``Operator[node]`` in the name stack: its own scopes, the ``jit`` functions
it calls and the primitive (``jit(argsort)/sort``, ``moe_gmm/pallas_call``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ms(table, row):
    return row["ms_per_step"] if table["steps"] else row["ms"]


def by_operator(table, out):
    """One line an operator, a column a pass."""
    from mxnet_tpu.profiler import PASSES

    grid, extra = {}, {}
    for row in table["by_operator"]:
        grid.setdefault(row["operator"], {})[row["pass"]] = _ms(table, row)
        e = extra.setdefault(row["operator"], {"calls": 0, "bytes": 0.0,
                                               "flops": 0.0, "xla": {}})
        e["calls"] += row["calls"]
        e["bytes"] += row.get("bytes", 0.0)
        e["flops"] += row.get("flops", 0.0)
        for kind, ms, n in row["xla"]:
            got = e["xla"].setdefault((kind, row["pass"]), [0.0, 0])
            got[0] += ms
            got[1] += n
    per = max(table["steps"], 1)
    out(f"{'operator':<22}" + "".join(f"{p:>10}" for p in PASSES)
        + f"{'total':>10}{'share':>7}{'calls':>8}{'GB':>8}{'GFLOP':>9}"
        "  most of it (kind pass ms calls)")
    for operator, row in sorted(grid.items(),
                                key=lambda kv: -sum(kv[1].values())):
        e = extra[operator]
        total = sum(row.values())
        top = sorted(e["xla"].items(), key=lambda kv: -kv[1][0])[:3]
        out(f"{operator:<22}"
            + "".join(f"{row.get(p, 0.0):>10.3f}" for p in PASSES)
            + f"{total:>10.3f}"
            + f"{100 * total * per / table['busy_ms']:>6.1f}%"
            + f"{e['calls'] / per:>8.0f}{e['bytes'] / per / 1e9:>8.2f}"
            + f"{e['flops'] / per / 1e9:>9.1f}  "
            + ", ".join(f"{k} {p} {ms / per:.2f} {n / per:.0f}"
                        for (k, p), (ms, n) in top))
    totals = {p: sum(r.get(p, 0.0) for r in grid.values()) for p in PASSES}
    out(f"{'all':<22}" + "".join(f"{totals[p]:>10.3f}" for p in PASSES)
        + f"{sum(totals.values()):>10.3f}")


def by_node(table, out, top):
    out(f"{'operator':<20}{'node':<44}{'pass':<10}{'ms':>9}{'share':>7}"
        f"{'calls':>7}{'GB':>8}")
    per = max(table["steps"], 1)
    for row in table["by_node"][:top]:
        out(f"{row['operator']:<20}{str(row['node'] or ''):<44}"
            f"{row['pass']:<10}{_ms(table, row):>9.3f}"
            f"{100 * row['share']:>6.1f}%{row['calls'] / per:>7.0f}"
            f"{row.get('bytes', 0.0) / per / 1e9:>8.2f}")


def by_inner(table, out, top):
    """One line a name stack inside the operator and pass, all its nodes
    together."""
    out(f"{'inside the operator':<64}{'pass':<10}{'ms':>9}{'share':>7}"
        f"{'calls':>7}  XLA instructions (name ms calls)")
    per = max(table["steps"], 1)
    for row in table["by_inner"][:top]:
        name = row["inner"] or "(no name stack of its own)"
        out(f"{name if len(name) < 64 else '..' + name[-60:]:<64}"
            f"{row['pass']:<10}{_ms(table, row):>9.3f}"
            f"{100 * row['share']:>6.1f}%{row['calls'] / per:>7.0f}  "
            + ", ".join(f"{k} {ms / per:.3f} {n / per:.0f}"
                        for k, ms, n in row["xla"]))
    out(f"{'all':<74}"
        f"{sum(_ms(table, r) for r in table['by_inner']):>9.3f}")


def by_program(table, out):
    out(f"{'program':<56}{'ms':>10}{'calls':>8}")
    for row in table["by_program"]:
        out(f"{row['program']:<56}{_ms(table, row):>10.3f}"
            f"{row['calls']:>8}")


def idle(table, out):
    gaps = table["idle"]
    out(f"idle {gaps['total_ms']:.3f} ms of the window, by the span the "
        "host was in:")
    for name, ms in gaps["by_span"].items():
        out(f"  {name:<28}{ms:>10.3f}")
    out("longest gaps (span, ms, ms after the window's start):")
    for name, ms, at in gaps["longest"]:
        out(f"  {name:<28}{ms:>10.3f}{at:>12.3f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--window", default=None)
    ap.add_argument("--by", choices=("operator", "node", "program", "idle",
                                     "inner"),
                    default=None,
                    help="one table (default: all but node and inner)")
    ap.add_argument("--operator", default=None,
                    help="the operator --by inner splits, e.g. MoE")
    ap.add_argument("--top", type=int, default=40,
                    help="rows of the by-node and by-inner tables")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if (args.by == "inner") != (args.operator is not None):
        ap.error("--by inner and --operator NAME go together")

    from mxnet_tpu import profiler

    table = profiler.device_table(args.trace, window=args.window,
                                  inner=args.operator)
    if args.json:
        print(json.dumps(table))
        return 0
    out = print
    out(f"window {table['window'] or 'whole trace'}: {table['steps']} steps, "
        f"device busy {table['busy_ms']:.3f} ms"
        + (f" ({table['busy_ms'] / table['steps']:.3f} a step)"
           if table["steps"] else "")
        + f"; unscoped {100 * table['unscoped_share']:.2f}%, booked through "
        f"a neighbour's scope {100 * table['inherited_share']:.2f}%")
    if "hint" in table:
        out("NOTE: " + table["hint"])
    for name in ((args.by,) if args.by else ("operator", "program", "idle")):
        out("")
        if name == "operator":
            by_operator(table, out)
            if table["unscoped"]:
                out("unscoped, by XLA kind: " + ", ".join(
                    f"{r['name']} {_ms(table, r):.3f}"
                    for r in table["unscoped"][:8]))
        elif name == "node":
            by_node(table, out, args.top)
        elif name == "inner":
            by_inner(table, out, args.top)
        elif name == "program":
            by_program(table, out)
        else:
            idle(table, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
