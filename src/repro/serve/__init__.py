from .cache import ResultCache, as_result_cache, request_key
from .engine import ServeEngine, Request
from .predict import (PredictionService, WorkloadRequest, predict_top500,
                      warm)

__all__ = ["ServeEngine", "Request", "PredictionService", "WorkloadRequest",
           "ResultCache", "as_result_cache", "request_key",
           "predict_top500", "warm"]
