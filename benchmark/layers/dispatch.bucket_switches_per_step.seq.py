"""bucketing.switch counter per step (prepare() of the next batch switches too)."""

from benchmark.lib import readers

NAME = "dispatch.bucket_switches_per_step.seq"
UNIT = "1/step"
LAYER = "host dispatch"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"
read = readers.counter_per_step("bucketing.switch")
