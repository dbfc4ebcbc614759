"""Jitted steps (serve/serve_step.py, serve/sharded/serve_step.py):
device milliseconds per call of the prefill-step program, from the
profiler trace."""
from harness.readers import step_ms


def read(ctx):
    return step_ms(ctx, "prefill_step", len(ctx["record"]["prefill"]))
