"""repro.api — the unified algorithm layer.

One MMProblem protocol, one FederationSpec, one scan-jitted init/step/run
driver behind SA-SSMM, FedMM, the naive parameter-space baseline, FedMM-OT
and the LM trainer. See README.md in this package for the paper-object ->
driver-knob map.
"""
from .problem import MMProblem, as_problem  # noqa: F401
from .spec import FederationSpec, participation_draw  # noqa: F401
from .topology import Topology  # noqa: F401
from .schedule import (decaying_stepsize, resolve_schedule,  # noqa: F401
                       schedule_length)
from .driver import (CohortPartial, CohortSlice, DriverState,  # noqa: F401
                     apply_partial, centralized_init, centralized_step,
                     clear_trajectory_cache, history_list, init,
                     mean_oracle_diag, run, step, variates_at_init)
