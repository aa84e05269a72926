"""The control fails the check: at a size the CPU holds, the same tiny cell's
answers read against its reference pass the limit, and the tokens that the
reference computed in fp8 ranks first do not."""
import pytest

import tiny
from bench import check, lm
from bench.drivers import serve

LIMIT = 0.03        # tiny cell: program 0 to 0.008, fp8 control 0.069 and up


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    ctx = tiny.context("closed", seed=seed)
    cell = serve.ServeCell(ctx)
    cell.build()
    cell.setup()
    for _ in range(4):
        cell.tick(cell.requests("chat", 4), 0.0, "chat")
    cell.free()
    w = lm.make_weights(ctx.config, seed, "hf")
    program = max(check.gaps(ctx.config, w, cell.served))
    control = max(check.gaps(ctx.config, w, cell.served, quant="fp8"))
    assert program <= LIMIT < control
