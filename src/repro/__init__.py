"""repro — "MPI Progress For All" (Zhou et al., 2024) as a JAX/TPU framework.

The package provides:

* ``repro.core``        — the paper's explicit, collated, interoperable
  progress engine (MPIX_Stream / MPIX_Async / MPIX_Request_is_complete
  analogues) driving every host-side async subsystem.
* ``repro.collectives`` — user-level collective schedules (paper §4.7)
  expressed as shard_map + ppermute state machines, plus overlapped and
  compressed gradient reduction.
* ``repro.models``      — the ten assigned architectures.
* ``repro.kernels``     — Pallas TPU kernels for the compute hot spots.
* ``repro.launch``      — meshes, the step builder, train/serve
  drivers.
"""

__version__ = "1.0.0"
