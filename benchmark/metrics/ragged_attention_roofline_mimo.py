"""Reader of the per-layer metric `ragged_attention_roofline_mimo`: the least time the chip could take for the window's calls of the ragged attention kernels (flops_mimo.walked_bytes: the pages of K and V they walked, `attn_live_pages_<kind>_total`, at the HBM's peak rate) over the kernels' device seconds, read from the trace summary's `mosaic:ragged_paged_attention` (full layers) and `mosaic:ragged_paged_attention_window` entries (%). Only the kinds whose entry is among the trace's top operations are counted, above and below. None where the trace names neither kernel."""

import flops_mimo

KERNELS = {"full": "mosaic:ragged_paged_attention",
           "window": "mosaic:ragged_paged_attention_window"}


def read(x):
    counters = x["raw"].get("counters", {})
    seconds = {kind: sum(s for name, s in x["trace"].get("top_ops", [])
                         if name.split(" ")[0] == kernel)
               for kind, kernel in KERNELS.items()}
    kinds = [k for k, s in seconds.items()
             if s and f"attn_live_pages_{k}_total" in counters]
    if not kinds:
        return None
    least = (flops_mimo.walked_bytes(x["config"], counters, kinds)
             / x["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / sum(seconds[k] for k in kinds)
