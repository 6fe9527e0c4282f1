from repro.kernels.bank_energy.ops import (bank_activity_stats,  # noqa: F401
                                           candidate_grid, exact_bank_stats,
                                           resolve_backend)
from repro.kernels.bank_energy.ref import (bank_energy_np,  # noqa: F401
                                           bank_energy_ref,
                                           exact_bank_stats_np,
                                           exact_bank_stats_ref)
from repro.kernels.bank_energy.kernel import (bank_energy_kernel,  # noqa: F401
                                              exact_bank_stats_kernel)
