"""Reader of the per-layer metric `latent_attention_roofline_pangu`: the least time the chip could take for the window's calls of the absorbed latent attention kernel, the larger of its FLOPs at the bf16 peak (flops_openpangu.attention_flops: every key every valid query token attended, `attn_latent_key_rows_total`) and its bytes at the HBM's peak (flops_openpangu.walked_bytes: the latent pages it walked, `attn_latent_pages_total`, 576 stated values a token, so padding counts against the kernel), over the kernel's device seconds, read from the trace summary's `mosaic:latent_paged_attention` entries (%). None where the trace does not name the kernel."""

import flops_openpangu

KERNEL = "mosaic:latent_paged_attention"


def read(x):
    counters = x["raw"].get("counters", {})
    seconds = sum(s for name, s in x["trace"].get("top_ops", [])
                  if name.split(" ")[0] == KERNEL)
    if not seconds or "attn_latent_key_rows_total" not in counters:
        return None
    cfg, peaks = x["config"], x["peaks"]
    least = max(flops_openpangu.attention_flops(cfg, counters)
                / peaks["bf16_flops_per_s"],
                flops_openpangu.walked_bytes(cfg, counters)
                / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
