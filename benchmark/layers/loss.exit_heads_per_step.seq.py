"""executor.exit_loss_heads counter per step: the exits whose logits a
launched train program's ``ExitSoftmaxOutput`` nodes read (``total_ut_steps``
a node: 4.0 in ``ouro-2.6b-train-1c``). 0 is the alarm that the looped model
fell back to one head under ``SoftmaxOutput``."""

from benchmark.lib import readers

NAME = "loss.exit_heads_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.exit_loss_heads")
