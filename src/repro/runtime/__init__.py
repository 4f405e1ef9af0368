"""repro.runtime — fault-tolerant training loop, and the program's spans
and counters (:mod:`repro.runtime.spans`)."""

from repro.runtime.trainer import Trainer, TrainerConfig, TrainerEvents

__all__ = ["Trainer", "TrainerConfig", "TrainerEvents"]
