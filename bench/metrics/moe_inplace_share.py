"""Share of the MoE layers of the decode calls that read their held experts
in place: the counter ``moe.inplace_layers`` over ``engine.decode_steps``
times the configuration's MoE layers, moved inside the ticks that ran whole
inside the traced slice, in %.  None on a program whose decode does not
count them, or on a configuration with no MoE layers."""
from bench import lm
from bench.metrics import _spans


def read(ctx, res):
    obs = _spans.program_obs()
    if obs is None or "moe.inplace_layers" not in obs.snapshot():
        return None
    m = lm.dims(ctx.config)
    if "dense_layers" not in m:     # a family with no MoE layers
        return None
    layers = m["layers"] - m["dense_layers"]
    calls = _spans.counted(ctx, res, "engine.decode_steps")
    if not calls or not layers:
        return None
    return (100.0 * _spans.counted(ctx, res, "moe.inplace_layers")
            / (calls * layers))
