"""finetune_controller_tpu — a TPU-native fine-tuning platform.

Two planes:

* **Compute plane** (``models``, ``ops``, ``parallel``, ``train``, ``data``):
  a JAX/XLA trainer with mesh/NamedSharding parallelism (DP/FSDP/TP; SP/EP in
  later tiers), LoRA adapters, Orbax checkpointing, and Pallas kernels where
  XLA defaults lose.  This is the part the reference
  (``acceleratedscience/finetune-controller``) delegated to user-supplied
  containers (see SURVEY.md §2.2) and is first-class here.

* **Control plane** (``control``, being built alongside): the capability
  surface of the reference —
  authenticated submit/queue/monitor/log-stream/metrics/promote of fine-tune
  jobs (reference ``app/main.py``) — rebuilt without its import-time cluster
  I/O warts (reference ``app/core/config.py:59-90``): every component is
  lazily constructed and injectable.
"""

# first, so that every import after this line is on the start-up log's clock
# (obs/trace.py::StartupLog — stdlib only, like all of ``obs``)
from .obs import trace as _trace

_trace.STARTUP.open()

__version__ = "0.1.0"
