"""Continuous-batching serving runtime."""
from repro.serving.engine import Engine, EngineStats, init_params
from repro.serving.request import Request, RequestStatus, SamplingParams
from repro.serving.sampler import sample_tokens
from repro.serving.scheduler import Scheduler, StepPlan

__all__ = ["Engine", "EngineStats", "init_params", "Request", "RequestStatus",
           "SamplingParams", "sample_tokens", "Scheduler", "StepPlan"]
