"""ray_tpu.serve.llm — continuous-batching LLM serving on TPU.

The engine (`engine.py`, the host scheduler) keeps a fixed pool of
decode slots inside a bounded set of compiled XLA programs
(`programs.py`, its device half); the deployment (`deployment.py`)
exposes it as a Serve replica; `kv_cache.py` pages the KV pool and
reuses shared prompt prefixes; `router.py` spreads requests across N
replicas on probed queue depth, SLO lane, and expected prefix-cache
hit (cluster-wide KV index); `disagg/` splits prefill and decode onto
separate replica pools with KV-block migration over the object store
and speculative decoding on the decode side. Evicted prefix blocks
spill down a memory hierarchy (HBM -> host RAM -> object store,
`KVTierManager`) and are promoted back through the adopt scatter when
`PromoteCostModel` says re-adopt beats re-prefill. See README "Serving
LLMs" / "Disaggregated serving" / "KV memory hierarchy" for the
design narrative, and PERF.md for what the benchmark's cells measure.

Models the engine serves, each through `config.serving()`
(models/serving.py) and each with a cell in the benchmark: the dense
GQA decoder (`models/llama.py`), latent attention with routed and
shared experts (`models/latent_moe.py`), recurrent delta-rule layers
with a state by slot beside latent attention (`models/kimi_linear.py`),
gated short convolutions beside GQA (`models/conv_moe.py`), and
sliding-window beside full attention over two kinds of paged pool
(`models/window_moe.py`: a table by position for the full layers, a
ring of blocks for the window layers, `kv_cache.WindowRing`).  The last
three stay in the slot they were admitted to: prefix reuse, spill,
export / adopt, preemption and speculation are refused for them by
name.
"""

from ray_tpu.serve.llm.deployment import LLMServer, build_llm_app
from ray_tpu.serve.llm.disagg import (
    DecodeServer, KVExporter, KVImporter, PrefillServer,
    build_disagg_llm_app,
)
from ray_tpu.serve.llm.engine import (
    EngineConfig, LLMEngine, Request, RequestHandle,
)
from ray_tpu.serve.llm.kv_cache import (
    BlockAllocator, KVPrefix, KVState, KVTierManager, PrefixCache,
    PromoteCostModel, TierHit, WindowRing, stable_hash_prefix,
)
from ray_tpu.serve.llm.router import LLMRouter, build_routed_llm_app

__all__ = [
    "BlockAllocator", "DecodeServer", "EngineConfig", "KVExporter",
    "KVImporter", "KVPrefix", "KVState", "KVTierManager", "LLMEngine",
    "LLMRouter", "LLMServer", "PrefillServer", "PrefixCache",
    "PromoteCostModel", "Request", "RequestHandle", "TierHit",
    "WindowRing", "build_disagg_llm_app", "build_llm_app", "build_routed_llm_app",
    "stable_hash_prefix",
]
