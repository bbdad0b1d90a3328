"""The benchmark's yardstick: cells as data, the closed loop of whole jobs,
the trace reduction, the bytes a step must move. Nothing here imports the
program; ``verbs/`` does."""
