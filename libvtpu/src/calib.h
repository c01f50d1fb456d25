// Calibration oracle: device-truth busy attestation via compiled
// known-duration probes.
//
// Why it exists (round-5 validation run 13):
// on a proxied PJRT runtime EVERY passively observed busy signal — D2H
// walls, completion-event intervals, attach probes — inflates with transport
// weather, so the sync-wall charger accreted four generations of
// compensators (floor, charge cap, weather band, event-fed budget) and a
// storm still charged one tenant 60.9 s of phantom duty. HAMi-core never
// faces this because it reads device-local counters in-process; a PJRT
// shim's equivalent of "go where the truth lives" is ACTIVE attestation:
//
//   at attach (pre-tenant-work, the same un-gameability argument as the
//   transport-floor probe) compile a calibration executable through the
//   real plugin (PJRT_Client_Compile, a chained-matmul loop sized to a few
//   ms of device time), run it K times solo and once as an N-deep chain,
//   and compare three clocks over the SAME known workload:
//
//     W_1 = wall of one run, completion-coupled via a D2H read-back (the
//           one signal even lying-event runtimes must keep honest — the
//           bytes have to arrive);
//     W_N = wall of the N-chain, same coupling;
//     E   = the completion EVENT's reported duration for one run.
//
//   The chain difference D = (W_N - W_1) / (N - 1) is the probe's device
//   duration with the transport round trip cancelled exactly (the same
//   two-chain-length trick mfu_bench uses), so:
//
//     T        = W_1 - D                 per-session idle-transport baseline
//     ratio    = D / E                   calibrated events->duty scale
//     verdict  = FAITHFUL           when E matches D (events are device truth;
//                                   the limiter charges event-settled busy as
//                                   the absolute reference — no band, no cap,
//                                   no sync-wall charging at all)
//                LYING              when E < D/2 (events claim less than half
//                                   the attested duration — enqueue-fulfilled
//                                   events; attestation FAILS and full-wall
//                                   charging persists, so the adversarial
//                                   bound survives: a lying-event tenant's
//                                   stretched calibration walls cannot match
//                                   its claimed event durations)
//                TRANSPORT_POLLUTED when E >> D (real completion events whose
//                                   delivery rides a proxy transport; the attested
//                                   baseline T is deducted from event settles
//                                   and the compensator tower stays engaged
//                                   as the explicit fallback)
//
// Re-attestation: a detached thread re-runs one probe every
// VTPU_CALIB_INTERVAL_MS (default 30 s) and DEMOTES a faithful verdict to
// LYING if the event channel starts claiming less than half the attested
// duration (demote-only: tenant queue depth can only inflate E_re, never
// deflate it, so there are no false demotions and no gameable promotions).
// Its duty cost is bounded (skipped above VTPU_CALIB_DUTY_PPM of wall time,
// default 0.5%) and self-charged through
// DutyCycleLimiter::charge_busy_unpaced — visible in the util window and the
// calib_probe_busy_ns export, but never a token debit, so calibration can
// never pace a tenant.
//
// Everything goes through the REAL api table, so tenant accounting (HBM cap,
// stats, execute counters) never sees the probes. Compile failure or a
// plugin without PJRT_Client_Compile leaves the verdict UNKNOWN and the
// fallback tower engaged — exactly the pre-calibration behavior.
#ifndef VTPU_CALIB_H_
#define VTPU_CALIB_H_

#include <cstdint>

#include "pjrt_c_api.h"

namespace vtpu {

class Region;
class DutyCycleLimiter;

namespace calib {

enum Verdict : int32_t {
  kUnknown = 0,
  kFaithful = 1,
  kLying = 2,
  kTransportPolluted = 3,
};

struct Snapshot {
  int32_t verdict = kUnknown;
  uint32_t fallback_engaged = 1;
  uint64_t ratio_ppm = 0;      // events->duty scale x 1e6 (D / E)
  uint64_t baseline_ns = 0;    // per-session idle-transport baseline T
  uint64_t probe_ns = 0;       // attested device duration D of one probe
  uint64_t recalibs = 0;       // re-attestation runs
  uint64_t probe_busy_ns = 0;  // cumulative self-charged probe device time
};

Snapshot snapshot();

// Lock-free hot-path check: true iff the verdict is live-verified FAITHFUL,
// i.e. event settles are the absolute busy reference and charge_sync_wall
// must not engage any band, cap, floor, or wall charge.
bool events_attested_faithful();

// The attested idle-transport baseline (0 until calibrated). Deducted from
// event-settle intervals on TRANSPORT_POLLUTED runtimes.
uint64_t transport_baseline_ns();
int32_t verdict();

// Run attach-time calibration on the freshly created client (first attach
// only — later attaches would let tenant work pollute the probes) and start
// the bounded re-attestation thread. `limiter`/`region` may be null.
void calibrate_at_attach(const PJRT_Api* real, PJRT_Client* client,
                         Region* region, DutyCycleLimiter* limiter);

// Stop re-attestation from touching the client (called before the real
// PJRT_Client_Destroy). A no-op unless `client` is the attested one — a
// tenant destroying some OTHER short-lived client must not tear down the
// oracle. The last verdict stays in force for the process.
void on_client_destroy(PJRT_Client* client);

// race_stress-only hook: hammer the shared state from a writer thread while
// readers call snapshot()/events_attested_faithful().
void set_state_for_stress(const Snapshot& s);

}  // namespace calib
}  // namespace vtpu

#endif  // VTPU_CALIB_H_
