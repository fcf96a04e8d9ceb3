"""pathway_tpu.serving — the always-on serving gateway.

The millions-of-users story (ROADMAP item 2): between ``pw.io.http``
ingress and the frontier runtime sit three subsystems —

* **admission control** (:mod:`.admission`) — per-route and per-tenant
  token buckets with bounded queues; over-limit requests get
  429 + Retry-After instead of unbounded pending futures;
* **watermark backpressure** (:mod:`.backpressure`) — the gateway reads
  the runtime's per-source watermark-lag gauges and sheds or paces
  admission when the pipeline's frontier falls behind ingress;
* **continuous batching** (:mod:`.continuous_batching`) — LLM decode
  runs as a slot scheduler over one persistent KV cache: new requests
  join in-flight batches at step boundaries instead of waiting for the
  wave to drain. ``JaxLMChat`` builds it at temperature 0; sampled
  generation keeps the wave-aligned coalescer.

Entry point: ``ServingGateway`` passed to ``rest_connector(gateway=...)``
(or to the ``xpacks.llm.servers`` REST servers). Docs: docs/serving.md §6.
"""

from pathway_tpu.serving.admission import (
    AdmissionController,
    AdmissionDecision,
    TokenBucket,
)
from pathway_tpu.serving.backpressure import WatermarkBackpressure
from pathway_tpu.serving.continuous_batching import ContinuousBatcher
from pathway_tpu.serving.gateway import ServingGateway

__all__ = [
    "AdmissionController",
    "AdmissionDecision",
    "ContinuousBatcher",
    "ServingGateway",
    "TokenBucket",
    "WatermarkBackpressure",
]
