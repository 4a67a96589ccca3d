from .energy import FrequencyController, SimulatedController, EnergyMeter, \
    StepEnergy
from .ft import FailureInjector, InjectedFailure, StragglerWatchdog, \
    HeartbeatRegistry, StragglerEvent

__all__ = [
    "FrequencyController", "SimulatedController", "EnergyMeter",
    "StepEnergy", "FailureInjector", "InjectedFailure",
    "StragglerWatchdog", "HeartbeatRegistry", "StragglerEvent",
]
