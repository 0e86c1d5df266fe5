"""Seconds BucketSentenceIter took to bucket and pad the sentences (rnn.bucket_iter_build span)."""

from benchmark.lib import spans

NAME = "setup.input_build_s"
UNIT = "s"
LAYER = "input plane"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_part(NAME)
