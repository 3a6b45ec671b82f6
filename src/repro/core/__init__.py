from repro.core.adjoint import odeint_adjoint
from repro.core.analogue import (AnalogueMLPVectorField, AnalogueSpec,
                                 analogue_matmul, analogue_mlp_apply,
                                 program_mlp, program_tensor)
from repro.core.losses import (dtw, l1, lyapunov_time,
                               max_lyapunov_exponent, mre, normalized_dtw,
                               soft_dtw, soft_dtw_batch)
from repro.core.backends import (AnalogueBackend, Backend, DigitalBackend,
                                 ExecState, FusedPallasBackend,
                                 resolve_backend)
from repro.core.node import (CDEVectorField, ContinuousDepthBlock,
                             MLPVectorField, NeuralODE, dense_linear,
                             mlp_apply, mlp_init)
from repro.core.ode import make_odeint, odeint, odeint_dopri5, rk4_step
from repro.core.twin import (DigitalTwin, TwinFleet, make_autonomous_twin,
                             make_cde_twin, make_driven_twin,
                             reference_trajectory,
                             simulate_batch)
