"""Device time of the jitted decode program per call, from the trace, in a
cell whose model has experts: ``decode_step_ms``'s reading."""
from bench.metrics.decode_step_ms import read  # noqa: F401
