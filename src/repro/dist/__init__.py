"""repro.dist — the distributed-communication substrate.

  sharding     parameter/batch/cache PartitionSpec rules + local shapes
  collectives  explicit ring allreduce, accounted lax wrappers, wire-byte
               tally
  quantize     symmetric int8 block quantization (fake + real int8 wire)
  packed       the packed sparse wire codec (bit-packed indices + int8
               values) shared by the fake and real packed exchanges
  transport    the Transport protocol (Mesh / Ring / Sim) the gradient
               compressors are written against
  chaos        seeded fault injection (ChaosTransport, chaos:<base>) +
               the guard observability channels (fault tally,
               structural sink, WireFaultError)
"""
from repro.dist.chaos import (
    GUARD_POLICIES,
    ChaosTransport,
    FaultSpec,
    WireFaultError,
    fault_report,
    raise_on_faults,
    reset_fault_tally,
)
from repro.dist.collectives import (
    all_gather,
    all_gather_packed,
    broadcast,
    hierarchical_ring_allreduce,
    pmean,
    psum,
    record_wire_bytes,
    reset_wire_tally,
    ring_allreduce,
    ring_allreduce_multi,
    ring_allreduce_q8,
    ring_broadcast,
    wire_report,
)
from repro.dist.sharding import (
    batch_pspec,
    cache_pspecs,
    keystr_path,
    local_shape,
    param_pspecs,
    partition_spec,
)
from repro.dist.transport import (
    EXACT_TOL,
    Q8_TOL,
    RING_TRANSPORTS,
    TRANSPORTS,
    MeshTransport,
    RingHierTransport,
    RingPackedTransport,
    RingQ8Transport,
    RingTransport,
    SimTransport,
    Transport,
    make_transport,
)
