"""paddle_tpu.serving — continuous batching, paged KV-cache, decode driver.

The million-user inference surface (ROADMAP item 1): where
``inference.predictor`` runs one fully-padded request at a time, this
package multiplexes a request stream onto a device-resident autoregressive
decode loop —

* :class:`~.scheduler.Scheduler`: bounded FIFO queue → fixed batch slots,
  with continuous (in-flight) admission each decode step,
* :class:`~.page_pool.PagePool` + :class:`~.kv_cache.PagedKVCache`: fixed
  HBM pages and per-request page tables, so ragged sequence lengths pay
  for pages, not padding (kernel blueprint: "Ragged Paged Attention",
  PAPERS.md; XLA-gather path in ``ops.attention_ops.decode_attention``),
* :class:`~.engine.ServingEngine`: AOT-compiled (``executor.aot_compile``)
  per-bucket prefill + fused decode steps with all serving state on device,
* ``serving/*`` monitor counters + latency histograms, flight-recorder
  capture of the in-flight batch on decode failure.

Quick start::

    from paddle_tpu import serving
    from paddle_tpu.models import decoder_lm

    model = decoder_lm.DecoderLM(decoder_lm.DecoderConfig(max_seq=128))
    eng = serving.ServingEngine(model, serving.ServingConfig(
        slots=8, page_size=16, max_seq=128))
    reqs = [eng.submit([1, 2, 3], max_new_tokens=16) for _ in range(32)]
    eng.run()                 # drains queue+slots, continuous batching
    print(reqs[0].tokens_out, reqs[0].latency_s)
    eng.close()               # releases the continuous-telemetry exporter

Measured by the serving cells of ``python3 -m grid.run`` (``BENCHMARK.json``).
"""

from . import trace  # noqa: F401
from .engine import ServingConfig, ServingEngine  # noqa: F401
from .kv_cache import (  # noqa: F401
    ContiguousKVCache, Int8PagedKVCache, LatentPagedCache, PagedKVCache)
from .page_pool import PagePool, PagePoolExhausted  # noqa: F401
from .request import (  # noqa: F401
    FAILED, FINISHED, QUEUED, REJECTED, RUNNING, TIMEOUT, BackpressureError,
    DrainingError, Request)
from .scheduler import Scheduler  # noqa: F401

__all__ = [
    "ServingConfig", "ServingEngine",
    "PagedKVCache", "Int8PagedKVCache", "LatentPagedCache",
    "ContiguousKVCache",
    "PagePool", "PagePoolExhausted",
    "Scheduler", "Request", "BackpressureError", "DrainingError",
    "QUEUED", "RUNNING", "FINISHED", "TIMEOUT", "FAILED", "REJECTED",
    "trace",
]
