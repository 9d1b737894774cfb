"""paddle_tpu.disagg — disaggregated prefill/decode serving.

The package splits the two inference phases onto separate engines and
streams finished KV pages between them through a host-RAM page store:

* ``pagestore`` — the store itself (radix-keyed page runs), the
  blockwise-int8 wire encoding (int8-KV pool pages ship VERBATIM;
  fp32 pages quantize one scale per (head, token-slot) — exactly the
  pool's scale-plane layout), the length-prefixed TCP server/client,
  and coordinator-env store discovery.
* ``roles`` — ``PrefillWorker`` (engine pinned to chunked prefill,
  publishes pages to the store), ``DecodeWorker`` (admission consults
  the store before cold prefill and resumes at the fork point), and
  ``DisaggService`` (the engine-shaped facade the traffic tier drives
  unchanged: admit once, prefill on the prefill pool, hand the ticket
  to the decode worker the ``paddle_generation_*`` gauges pick).

Because the decode worker re-derives the first output token from the
spliced prefix, the split topology is token-identical to co-located
greedy serving — bit-identical with int8 KV pools or
``disagg_wire_encoding="raw"`` (tests/test_disagg.py gates this, the
wire bytes <= 0.3x fp32 and the warm start of a fresh decode worker;
no cell of the benchmark times the split topology yet).
"""

from __future__ import annotations

from .pagestore import (HostPageStore, PageStoreClient, PageStoreServer,
                        decode_page, discover_store, encode_page,
                        fp32_page_bytes, run_for_pool,
                        store_endpoint_from_env)
from .roles import DecodeWorker, DisaggService, DisaggStream, PrefillWorker

__all__ = [
    "HostPageStore", "PageStoreServer", "PageStoreClient",
    "encode_page", "decode_page", "run_for_pool", "fp32_page_bytes",
    "store_endpoint_from_env", "discover_store",
    "PrefillWorker", "DecodeWorker", "DisaggService", "DisaggStream",
]
