"""llm-d-tpu: a TPU-native distributed LLM inference serving framework.

Capability parity target: the llm-d stack (reference: /root/reference, an
umbrella repo binding vLLM + inference scheduler (EPP) + routing sidecar +
NIXL/DeepEP transports into three "well-lit paths").  This package provides
TPU-first equivalents of every executable layer:

  - ``engine``    : the JAX serving engine (paged KV, continuous batching)
                    -- the vLLM equivalent (reference: docker/Dockerfile.cuda:61-63).
  - ``models``    : dense (Llama/Qwen) and MoE (DeepSeek/Mixtral-style) families.
  - ``ops``       : attention / sampling / MoE ops; Pallas TPU kernels with
                    jnp references (FlashInfer/DeepGEMM equivalents).
  - ``parallel``  : device mesh, sharding rules, collectives (NCCL/NVSHMEM
                    equivalents collapse into XLA collectives over ICI).
  - ``kv``        : KV-connector abstraction, P->D transfer, tiered offload,
                    KV events (NIXL / LMCache / OffloadingConnector equivalents).
  - ``server``    : OpenAI-compatible HTTP server with the vllm:* metric
                    names and the three-probe contract
                    (reference: docs/readiness-probes.md).
  - ``epp``       : endpoint-picker scheduler: plugin pipeline of profile
                    handlers / filters / scorers / pickers
                    (reference: llm-d-inference-scheduler v0.4.0).
  - ``sidecar``   : routing proxy orchestrating prefill/decode disaggregation
                    (reference: llm-d-routing-sidecar v0.4.0).
  - ``sim``       : accelerator-free inference simulator
                    (reference: llm-d-inference-sim v0.6.1).
  - ``autoscale`` : saturation-based workload-variant autoscaler
                    (reference: workload-variant-autoscaler).
  - ``predictor`` : online TTFT/TPOT latency predictors
                    (reference: guides/predicted-latency-based-scheduling).
"""

__version__ = "0.1.0"
