"""executor.linear_attention_chunks counter per step: the chunks the
linear-attention layers of a launched train program cut their rows into,
batch x T / chunk a layer: the trips of the scan that carries the state
between chunks. 384 in the qwen3-next cell (3 layers x 8192 / 64); T a layer
would mean the recurrence runs a token at a time, 0 that the path is gone."""

from benchmark.lib import readers

NAME = "linear_attention.chunks_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.linear_attention_chunks")
