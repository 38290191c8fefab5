"""The benchmark's yardstick: data, reference, comparison, traces, metric
arithmetic.  Nothing here imports ``repro`` except :mod:`yardstick.serve`,
which adapts query descriptions to the program's query objects."""
