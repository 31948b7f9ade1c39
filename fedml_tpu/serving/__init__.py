"""Model serving (reference: python/fedml/serving/ + model_scheduler/)."""

from .admission import AdmissionController, AdmissionError, TenantPolicy
from .continuous_batching import PagedContinuousBatchingEngine
from .endpoint import Endpoint, EndpointManager, ModelCard, ModelDB
from .fedml_inference_runner import FedMLInferenceRunner
from .fedml_predictor import FedMLPredictor, JaxPredictor
from .paged_kv import PagedKVAllocator

__all__ = [
    "AdmissionController",
    "AdmissionError",
    "TenantPolicy",
    "PagedContinuousBatchingEngine",
    "Endpoint",
    "EndpointManager",
    "ModelCard",
    "ModelDB",
    "FedMLInferenceRunner",
    "FedMLPredictor",
    "JaxPredictor",
    "PagedKVAllocator",
]
