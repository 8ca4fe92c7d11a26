"""Network presets from the paper's test environments (Tables 1-2) plus the
TPU-fabric DCN preset used by the grad-sync adaptation.

Calibration targets (paper observations the simulator should land near):
  - Fig 1a/2a: pipelining gives up to ~2x on small files, negligible on large
    -> per-file ``unhidden_overhead`` comparable to the control RTT on the
       Fig-1 XSEDE pair (RTT 60 ms).
  - Fig 1c: one channel moves ~2 Gbps of huge files on XSEDE; eight channels
    reach ~9 Gbps  -> window_efficiency*buf/RTT ~ 2.3 Gbps, disk lanes ~4 Gbps.
  - Fig 9a (BlueWaters-Stampede, 3x10G, DES): MC/ProMC peak ~22 Gbps at CC 8,
    declining beyond (disk contention); Globus Online <= 8.5 Gbps.
  - Fig 9b (Stampede-Comet): MC/ProMC up to ~8.6 Gbps.
  - Fig 9c (SuperMIC-Bridges): 4 MB TCP buffer + server-side stream clamp
    => concurrency keeps helping; ~4 Gbps at high maxCC.
  - Fig 6 (LAN, GlusterFS on 5 servers): >2 Gbps; dips when maxCC > 4.
  - Fig 13: Globus Connect Personal on LAN ~500 Mbps (relay path).
"""
from __future__ import annotations

import dataclasses
import math

from .types import MB, DiskSpec, NetworkSpec, gbps

#: Ethernet-ish segment size used by the Mathis loss-window model below.
_MSS = 1460.0


def impaired_variant(
    base: NetworkSpec,
    name: str,
    *,
    loss_rate: float = 0.0,
    jitter: float = 0.0,
    control_rtt: float | None = None,
    bandwidth_steps: tuple | None = None,
    bandwidth_ramp: tuple | None = None,
) -> NetworkSpec:
    """Derive a pathologically impaired path from a clean testbed preset.

    loss_rate     random segment loss. Per-stream TCP throughput follows the
                  Mathis bound ``MSS/(RTT*sqrt(loss))`` — modeled by capping
                  the effective window at ``MSS * sqrt(1.5/loss)`` bytes, so
                  parallelism (many small windows) becomes the decisive
                  knob, exactly the regime the paper's Sec. 3 argues for.
    jitter        RTT variance. Ack clocking keys on the worst-case RTT, so
                  the window-limited rate sees ``rtt + 2*jitter`` and the
                  per-file command gap inherits the same inflation.
    control_rtt   asymmetric routes: the control channel's round trip when
                  it differs from the data path (satellite uplink, congested
                  reverse path). Inflates per-file dead time only; omit to
                  keep the base path's (a)symmetry.
    bandwidth_steps
                  time-varying capacity ("network conditions vary over
                  time"): ``((t, mult), ...)`` piecewise-constant capacity
                  multipliers; a leading ``(0.0, 1.0)`` step is prepended
                  if missing. Tuning (Algorithm 1) and rate predictions
                  keep using the nominal bandwidth — the realized rates
                  deviating from the plan is precisely what the adaptive
                  controllers must react to.
    bandwidth_ramp
                  ``(t0, t1, end_scale, n_steps)``: a linear capacity drift
                  from 1.0 at ``t0`` to ``end_scale`` at ``t1``, rendered
                  as a dense step ladder (fluid integration stays exact on
                  piecewise-constant rates on every backend).
    """
    rtt = base.rtt + 2.0 * jitter
    buffer_size = base.buffer_size
    window_efficiency = base.window_efficiency
    if loss_rate > 0.0:
        mathis_window = _MSS * math.sqrt(1.5 / loss_rate)
        buffer_size = int(min(buffer_size, mathis_window))
        # loss recovery also wastes a slice of whatever window remains
        window_efficiency *= 0.9
    fields = dict(
        name=name,
        rtt=rtt,
        buffer_size=buffer_size,
        window_efficiency=window_efficiency,
        unhidden_overhead=base.unhidden_overhead + jitter,
    )
    if control_rtt is not None:  # else inherit the base's control path
        fields["control_rtt"] = control_rtt
    if bandwidth_steps is not None and bandwidth_ramp is not None:
        raise ValueError("pass bandwidth_steps or bandwidth_ramp, not both")
    if bandwidth_ramp is not None:
        t0, t1, end_scale, n_steps = bandwidth_ramp
        bandwidth_steps = tuple(
            (t0 + i * (t1 - t0) / n_steps, 1.0 + (end_scale - 1.0) * i / n_steps)
            for i in range(1, n_steps + 1)
        )
    if bandwidth_steps is not None:
        prof = tuple((float(t), float(m)) for t, m in bandwidth_steps)
        if not prof or prof[0][0] > 0.0:
            prof = ((0.0, 1.0),) + prof
        fields["bandwidth_profile"] = prof
    return dataclasses.replace(base, **fields)

# ---------------------------------------------------------------------------
# Table 1 environments (Sec. 3 parameter-effect experiments, Figs. 1-2)
# ---------------------------------------------------------------------------

XSEDE = NetworkSpec(
    name="xsede-lonestar-gordon",
    bandwidth=gbps(10),
    rtt=60e-3,
    buffer_size=32 * MB,
    # "highly tuned and parallelized disk sub-systems at the XSEDE sites"
    disk=DiskSpec(
        streaming_rate=gbps(9.8),
        per_file_overhead=0.004,
        saturation_cc=8,
        contention=0.02,
        per_channel_rate=gbps(4.0),
    ),
    # per-file server-side cost pipelining cannot hide; RTT-comparable so the
    # small-file pipelining win tops out near 2x (Fig. 1a).
    unhidden_overhead=0.055,
)

LONI = NetworkSpec(
    name="loni-queenbee-painter",
    bandwidth=gbps(10),
    rtt=10e-3,
    buffer_size=16 * MB,
    disk=DiskSpec(
        streaming_rate=gbps(5.5),
        per_file_overhead=0.005,
        saturation_cc=8,
        contention=0.03,
        per_channel_rate=gbps(2.5),
    ),
    unhidden_overhead=0.009,
)

# ---------------------------------------------------------------------------
# Table 2 environments (Sec. 4 performance comparison, Figs. 5-13)
# ---------------------------------------------------------------------------

BLUEWATERS_STAMPEDE = NetworkSpec(
    name="bluewaters-stampede",
    bandwidth=gbps(30),  # 3 x 10 Gbps
    rtt=32e-3,
    buffer_size=32 * MB,
    disk=DiskSpec(
        streaming_rate=gbps(24),
        per_file_overhead=0.004,
        saturation_cc=8,
        contention=0.05,  # visible decline past CC=8 (Fig. 9a)
        per_channel_rate=gbps(2.75),
    ),
    unhidden_overhead=0.012,
)

STAMPEDE_COMET = NetworkSpec(
    name="stampede-comet",
    bandwidth=gbps(10),
    rtt=40e-3,
    buffer_size=32 * MB,
    disk=DiskSpec(
        streaming_rate=gbps(9.2),
        per_file_overhead=0.004,
        saturation_cc=8,
        contention=0.02,
        per_channel_rate=gbps(2.3),
    ),
    unhidden_overhead=0.012,
)

SUPERMIC_BRIDGES = NetworkSpec(
    name="supermic-bridges",
    bandwidth=gbps(10),
    rtt=45e-3,
    buffer_size=4 * MB,  # sub-optimal; needs >50MB (Sec 4.2) => CC keeps helping
    disk=DiskSpec(
        streaming_rate=gbps(5.0),
        per_file_overhead=0.005,
        saturation_cc=12,
        contention=0.01,
        per_channel_rate=gbps(0.8),
    ),
    unhidden_overhead=0.012,
    max_streams_per_channel=2,  # server-side clamp; cumulative buffer only
    # grows with concurrency (Sec. 4.2 explanation of Fig. 9c)
)

LAN = NetworkSpec(
    name="didclab-lan-glusterfs",
    bandwidth=gbps(10),
    rtt=0.2e-3,
    buffer_size=1 * MB,
    # storage backed by only five servers (Sec. 4.1) -> early saturation and
    # contention past maxCC=4 (Fig. 6)
    disk=DiskSpec(
        streaming_rate=gbps(3.2),
        per_file_overhead=0.003,
        saturation_cc=4,
        contention=0.08,
        per_channel_rate=gbps(0.9),
    ),
    unhidden_overhead=0.004,
)

# ---------------------------------------------------------------------------
# Impaired-path variants (loss / jitter / asymmetric RTT) — the conditions
# HARP-style sweeps (arXiv:1708.03053) and the two-phase model
# (arXiv:1812.11255) tune against. These widen the full evaluation matrix
# beyond the paper's clean research WANs.
# ---------------------------------------------------------------------------

#: transatlantic-grade path with residual random loss: per-stream windows
#: collapse to the Mathis bound, so parallelism decides everything.
LOSSY_TRANSATLANTIC = impaired_variant(
    STAMPEDE_COMET,
    "lossy-transatlantic",
    loss_rate=2e-4,
)
LOSSY_TRANSATLANTIC = dataclasses.replace(LOSSY_TRANSATLANTIC, rtt=90e-3)

#: overlay/VPN path with heavy RTT variance: ack clocking keys on the
#: worst-case RTT and the per-file command gap inflates with it.
JITTERY_OVERLAY = impaired_variant(
    XSEDE,
    "jittery-overlay",
    jitter=12e-3,
)

#: asymmetric route: clean 20 ms data path, but control traffic rides a
#: congested 180 ms reverse path — pipelining (not parallelism) is the
#: decisive knob because only the per-file command gap is inflated.
ASYM_CONTROL_PATH = impaired_variant(
    dataclasses.replace(LONI, rtt=20e-3),
    "asym-control-path",
    control_rtt=180e-3,
)

# ---------------------------------------------------------------------------
# Time-varying capacity variants ("network conditions vary over time"):
# cross traffic steps the shared backbone down and partially back; an
# evening drain ramps the path away under the transfer. Step times sit
# inside the matrix's typical transfer spans (median ~35 s, p75 ~75 s) so
# the capacity actually moves mid-transfer, not before or after it.
# ---------------------------------------------------------------------------

#: backbone sharing a burst of cross traffic: drops to 45% twelve seconds
#: in, partially recovers, then settles degraded.
STEPPY_BACKBONE = impaired_variant(
    STAMPEDE_COMET,
    "steppy-backbone",
    bandwidth_steps=((12.0, 0.45), (45.0, 0.8), (120.0, 0.6)),
)

#: evening-congestion drain: capacity ramps linearly to 40% between
#: t=8 s and t=88 s (an 8-step ladder), then stays there.
RAMPY_EVENING = impaired_variant(
    LONI,
    "rampy-evening",
    bandwidth_ramp=(8.0, 88.0, 0.4, 8),
)

# ---------------------------------------------------------------------------
# TPU-fabric adaptation presets: the paper's tuning problem mapped onto an
# ML cluster's own transfers. Gradient buckets crossing the data-center
# network between pods, and checkpoint shards going to object storage,
# are the "files"; a collective group or an upload stream is a channel.
# ---------------------------------------------------------------------------

#: Cross-pod data-center network, as seen by one pod's gradient-sync engine.
#: "files" are gradient buckets; the channel window plays the TCP-buffer role.
DCN = NetworkSpec(
    name="tpu-dcn-pod-pair",
    bandwidth=25e9,  # 25 GB/s aggregate per pod pair
    rtt=500e-6,
    buffer_size=4 * MB,  # per-channel in-flight window (staging buffer)
    disk=DiskSpec(
        streaming_rate=700e9,  # HBM-side staging, far from binding
        per_file_overhead=20e-6,
        saturation_cc=64,
        contention=0.001,
        per_channel_rate=80e9,
    ),
    unhidden_overhead=50e-6,  # per-bucket collective launch overhead
    channel_setup_cost=5e-3,  # collective-group re-materialization
    window_efficiency=1.0,  # lossless fabric: no TCP dynamics
)

#: Host <-> distributed checkpoint storage path.
CKPT_STORE = NetworkSpec(
    name="ckpt-object-store",
    bandwidth=10e9,  # 10 GB/s per host aggregate
    rtt=2e-3,
    buffer_size=8 * MB,
    disk=DiskSpec(
        streaming_rate=8e9,
        per_file_overhead=0.002,
        saturation_cc=16,
        contention=0.01,
        per_channel_rate=1.2e9,
    ),
    unhidden_overhead=1e-3,
    window_efficiency=0.9,
)

TESTBEDS = {
    t.name: t
    for t in (
        XSEDE,
        LONI,
        BLUEWATERS_STAMPEDE,
        STAMPEDE_COMET,
        SUPERMIC_BRIDGES,
        LAN,
        LOSSY_TRANSATLANTIC,
        JITTERY_OVERLAY,
        ASYM_CONTROL_PATH,
        STEPPY_BACKBONE,
        RAMPY_EVENING,
        DCN,
        CKPT_STORE,
    )
}
