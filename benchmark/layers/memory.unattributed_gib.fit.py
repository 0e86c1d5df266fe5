"""The run's memory_peak_bytes (device.peak_hbm_gib.*: peak_bytes_in_use +
peak_bytes_reserved of the fullest chip) less the heaviest train program's
arguments, kept outputs, temporaries and code: what that program's own
analysis does not cover. Earlier generations of kept outputs still alive at
the peak, other loaded programs' scratch, the metric's state, the harness's
seeded-leaves reserve. With memory.step_arguments_gib.*, step_kept_outputs
and step_scratch it sums to device.peak_hbm_gib.* by construction. None
where the program has no such gauges."""

from benchmark.lib import harness as hx
from benchmark.lib.readers import GIB

NAME = "memory.unattributed_gib.fit"
UNIT = "GiB"
LAYER = "fused step"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    o = run["obs"]
    parts = [hx.tm_leaf(o["tm1"], name) for name in (
        "executor.program_argument_bytes",
        "executor.program_kept_output_bytes",
        "executor.program_temp_bytes", "executor.program_code_bytes")]
    if None in parts:
        return None
    return (o["memory_peak_bytes"] - sum(g["value"] for g in parts)) / GIB
