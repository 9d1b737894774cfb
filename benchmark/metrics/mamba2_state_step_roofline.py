"""Reader of the per-layer metric `mamba2_state_step_roofline`: the least time the chip could take for the window's calls of the `mamba2_state_step` kernel (flops_hybrid.state_step_call: bound by the bytes of the state read and written; one call a Mamba layer a step) over the kernel's device seconds, read from the trace summary's `mosaic:mamba2_state_step` entry (%). None where the trace names no such kernel."""

import flops_hybrid


def read(x):
    seconds = sum(s for name, s in x["trace"].get("top_ops", [])
                  if name.startswith("mosaic:mamba2_state_step"))
    steps = x["raw"].get("ragged_steps")
    if not seconds or not steps:
        return None
    cfg = x["config"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    flops, moved = flops_hybrid.state_step_call(cfg)
    least = flops_hybrid.roofline_seconds(flops, moved, x["peaks"])
    return 100.0 * steps * kinds.count("mamba") * least / seconds
