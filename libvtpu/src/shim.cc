// libvtpu: PJRT-level HBM-cap + core-duty-cycle enforcement for shared TPUs.
//
// The TPU-native re-design of the reference's HAMi-core CUDA intercept
// (SURVEY §2.4): instead of hooking cuMemAlloc/NVML via LD_PRELOAD symbol
// interposition, vtpu wraps the PJRT C API function table that every modern
// TPU workload (JAX/XLA via libtpu) goes through:
//
//   - delivery A (LD_PRELOAD): interpose dlopen/dlsym; when anything resolves
//     "GetPjrtApi" we hand out our wrapper (jax loads libtpu with
//     dlopen+dlsym, so this catches unmodified workloads);
//   - delivery B (plugin shadowing): libvtpu.so itself exports GetPjrtApi and
//     loads the real plugin from $VTPU_REAL_LIBTPU — point TPU_LIBRARY_PATH
//     at libvtpu.so and no preload is needed.
//
// Enforcement:
//   - HBM cap: every BufferFromHostBuffer is size-estimated (dtype x dims)
//     and rejected with a tagged RESOURCE_EXHAUSTED PJRT_Error once the
//     per-device cap (TPU_DEVICE_MEMORY_LIMIT_<i>) would be exceeded;
//     execute outputs are accounted from their real on-device sizes;
//     Buffer_Destroy releases accounting.
//   - Core percent: DutyCycleLimiter paces LoadedExecutable_Execute
//     submissions (queue-level pacing; TPUs have no SM-mask analog).
//   - QoS: priority gate + usage telemetry via the mmap'ed shared region the
//     node monitor reads (vtpu/monitor).
//
// ABI safety: the PJRT_Api struct is append-only; every wrapped field offset
// is bounds-checked against the runtime struct_size before being touched.

#include <dlfcn.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#include "calib.h"
#include "limits.h"
#include "limiter.h"
#include "log.h"
#include "region.h"
#include "pjrt_c_api.h"

namespace vtpu {
namespace {

// ------------------------------------------------------------- hot-path stats
//
// Per-wrapper cumulative costs. Over a proxied PJRT plugin every
// metadata call (Buffer_OnDeviceSizeInBytes, Memory_Kind, ...) can be a
// network round-trip, and size queries on fresh execute outputs may block
// until the buffer is *defined* — turning an async enqueue into a synchronous
// wait. These counters let bench.py attribute interception overhead
// (BASELINE.md "libvtpu overhead" note) instead of guessing.

uint64_t tick_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
}

struct Stats {
  std::atomic<uint64_t> executes{0};
  std::atomic<uint64_t> gate_ns{0};        // priority-gate wait
  std::atomic<uint64_t> admit_ns{0};       // duty-cycle limiter admit
  std::atomic<uint64_t> enqueue_ns{0};     // real PJRT execute call
  std::atomic<uint64_t> onready_ns{0};     // completion-event hook setup
  std::atomic<uint64_t> acct_ns{0};        // output accounting (total)
  std::atomic<uint64_t> size_rpcs{0};      // Buffer_OnDeviceSizeInBytes calls
  std::atomic<uint64_t> size_rpc_ns{0};
  std::atomic<uint64_t> numout_rpc_ns{0};  // NumOutputs resolution (cold only)
  std::atomic<uint64_t> memkind_rpcs{0};   // Memory_Kind calls
  std::atomic<uint64_t> memkind_rpc_ns{0};
  std::atomic<uint64_t> uploads{0};
  std::atomic<uint64_t> upload_ns{0};      // wrapped BufferFromHostBuffer total
  std::atomic<uint64_t> upload_real_ns{0}; // real plugin portion of uploads
  std::atomic<uint64_t> region_ns{0};      // shared-region writes
  std::atomic<uint64_t> size_cache_hits{0};
  std::atomic<uint64_t> size_cache_misses{0};
  std::atomic<uint64_t> settles{0};          // completion-event settlements
  std::atomic<uint64_t> settled_busy_ns{0};  // busy time those observed
  std::atomic<uint64_t> tohost_calls{0};     // D2H reads (the sync point on
  std::atomic<uint64_t> tohost_ns{0};        //   runtimes with eager events)
  std::atomic<uint64_t> await_calls{0};
  std::atomic<uint64_t> await_ns{0};
  // Charge-cap gate outcomes (r5): which leg a D2H wall's cap eligibility
  // failed on, plus how much wall time actually reached the limiter — the
  // artifact-level audit for "where do residual admit waits come from".
  // Reconciliation semantics: gate-veto counters (inflight/size/multichip)
  // count at SUBMIT unconditionally; charge-outcome counters
  // (capped/floored/uncapped) partition the cap-eligible calls at
  // COMPLETION and only accrue while enforcement or a region is active
  // (charge_sync_wall returns early otherwise); d2h_errors counts
  // call/event failures and OVERLAPS both groups (an errored call also
  // lands in its veto or outcome counter). So on an enforced, error-free
  // run: tohost_calls ~= vetoes + outcomes; errors and unenforced phases
  // account for any shortfall.
  std::atomic<uint64_t> d2h_capped{0};        // cap applied
  std::atomic<uint64_t> d2h_floored{0};       // wall fully under the floor
  std::atomic<uint64_t> d2h_uncapped{0};      // charged in full (scale test
                                              //   failed, or floor==0)
  std::atomic<uint64_t> d2h_gate_inflight{0};  // another own D2H in flight
  std::atomic<uint64_t> d2h_gate_size{0};     // size unknown or > 256 KiB
  std::atomic<uint64_t> d2h_gate_multichip{0};  // multi-chip assignment veto
  std::atomic<uint64_t> d2h_errors{0};        // call or event errored
  std::atomic<uint64_t> sync_charged_ns{0};   // ns actually charged from walls
  // Calibration-oracle outcome (calib.h): gated D2H walls skipped entirely
  // because events are live-verified faithful. On an attested runtime this
  // REPLACES the capped/floored/uncapped partition in the reconciliation
  // above — tohost_calls ~= vetoes + attested skips there.
  std::atomic<uint64_t> d2h_attested{0};
};

Stats& stats() {
  static Stats* s = new Stats();
  return *s;
}

struct ScopedNs {
  std::atomic<uint64_t>& acc;
  uint64_t t0;
  explicit ScopedNs(std::atomic<uint64_t>& a) : acc(a), t0(tick_ns()) {}
  ~ScopedNs() { acc.fetch_add(tick_ns() - t0, std::memory_order_relaxed); }
};

// --------------------------------------------------------- transport floor
// Auto-calibrated dispatch-RTT floor (the reference's CUDA_DEVICE_SM_LIMIT
// needs no operator tuning; neither should the core knob here). Over a
// proxied PJRT plugin, every completion-coupled wall the sync-wall
// charger sees carries the transport round trip — which is not chip busy.
//
// The calibration signal is the shim's OWN attach-time probe
// (probe_transport_floor): a tiny upload + device-to-host read-back, waited
// to transfer completion, on the freshly attached client BEFORE any tenant
// work exists. That wall is pure transport (the read-back has no compute
// ahead of it and moves 256 bytes) and is un-gameable — the tenant hasn't
// run yet. Tenant-call-derived signals were tried and rejected (r4):
// small-UPLOAD walls measure ~0.2 ms on the proxied dev runtime (its H2D is
// pipelined; only D2H completion carries the RTT), and tenant D2H walls
// include whatever compute the tenant queued — a min over them misreads
// constant-cost real work as floor, exactly the failure the core-share
// proportionality proof would hit.
//
// Floor = MINIMUM probe wall (min, not mean: congestion makes samples
// slower, never faster). The floor is attach-time-static thereafter:
// transport drift upward over-charges duty (conservative, in the limit's
// favor); drift downward under-charges, bounded by the caps below.
//
// Adversarial / staleness bounds: the floor is clamped to
// VTPU_CHARGE_FLOOR_MAX_MS (operator ceiling, default 1 s), every wall
// pays at least 1/16 regardless of floor, and bucket aging (kMaxAgeNs) is
// retained for any future periodic re-probe.
//
// r5: the floor stays ATTACH-PROBE-ONLY. On a shared relay the ambient
// round trip rises and jitters with concurrent sessions' traffic —
// queueing that is transport, not this tenant's chip busy
// (round-5 isolation run: concurrent sessions on this rig contend in the
// relay, never on chip) — and a static idle floor charges that jitter as
// duty, pacing tenants whose true device busy is <1%
// (round-5 validation run 1: 20-40 s admit waits at 0.2% measured duty).
// Two repairs were tried:
//  (a) feeding gated tenant D2H walls into this min-floor — rejected
//      twice over: a steady 1:1 tenant's walls converge the min on
//      RTT+compute (the constant-work misread r4 documented), and
//      round-5 validation run 3 caught the dual failure mode live: ONE
//      transiently-fast wall (57 ms on a ~97 ms session) stuck as the
//      bucket min — sparse samples never rotate it out — halving the
//      floor AND the floor-scaled cap threshold below, which re-enabled
//      full-wall charging mid-run;
//  (b) the charge-side cap in charge_sync_wall — kept: gated walls
//      charge at most their provable own compute (pending executes x the
//      event-fed EMA estimate), with eligibility scale-tested against
//      this stable attach floor. Jitter is absorbed per-wall by the cap
//      instead of being subtracted by a drifting floor, so no tenant
//      sample can ever move the floor, in either direction.
// Upward transport drift within a session is likewise absorbed by the
// cap for gated walls (drift excess stays under the scale test); ungated
// bursts over-charge conservatively, in the limit's favor.
class RttFloor {
 public:
  static constexpr int kMinSamples = 4;
  static constexpr int kBucketSamples = 64;
  static constexpr uint64_t kRotateNs = 30ull * 1000'000'000;
  // attach-time probes must not age out over a long-lived process: the
  // fallback to "charge full walls" would silently re-throttle transport
  static constexpr uint64_t kMaxAgeNs = UINT64_MAX;

  void record(uint64_t wall_ns, uint64_t now_ns) {
    std::lock_guard<std::mutex> lock(mu_);
    if (cur_n_ == 0) cur_start_ns_ = now_ns;
    if (wall_ns < cur_min_) cur_min_ = wall_ns;
    cur_last_ns_ = now_ns;
    if (++cur_n_ >= kBucketSamples || now_ns - cur_start_ns_ >= kRotateNs) {
      prev_min_ = cur_min_;
      prev_n_ = cur_n_;
      prev_last_ns_ = cur_last_ns_;
      cur_min_ = UINT64_MAX;
      cur_n_ = 0;
    }
  }

  // 0 (charge full walls) until enough FRESH samples have been seen.
  uint64_t floor_ns(uint64_t now_ns) {
    std::lock_guard<std::mutex> lock(mu_);
    bool cur_fresh = cur_n_ > 0 && now_ns - cur_last_ns_ <= kMaxAgeNs;
    bool prev_fresh = prev_n_ > 0 && now_ns - prev_last_ns_ <= kMaxAgeNs;
    int n = (cur_fresh ? cur_n_ : 0) + (prev_fresh ? prev_n_ : 0);
    if (n < kMinSamples) return 0;
    uint64_t m = UINT64_MAX;
    if (cur_fresh && cur_min_ < m) m = cur_min_;
    if (prev_fresh && prev_min_ < m) m = prev_min_;
    return m == UINT64_MAX ? 0 : m;
  }

 private:
  std::mutex mu_;
  uint64_t cur_min_ = UINT64_MAX;
  uint64_t prev_min_ = UINT64_MAX;
  uint64_t cur_start_ns_ = 0;
  uint64_t cur_last_ns_ = 0;
  uint64_t prev_last_ns_ = 0;
  int cur_n_ = 0;
  int prev_n_ = 0;
};

RttFloor& rtt_floor() {
  static RttFloor* f = new RttFloor();
  return *f;
}

// Charge-cap gate state (see RttFloor AMBIENT notes above). The counter
// measures executes since the last D2H SUBMISSION, but work submitted
// before the PREVIOUS fetch may still be draining on device when this one
// runs (a D2H waits only for its own buffer's producer), so the provable
// bound on compute hiding in a wall is the executes of the last TWO
// submission windows — g_prev_execs carries the prior window's count
// forward into the cap budget.
std::atomic<int> g_d2h_inflight{0};
std::atomic<uint32_t> g_execs_since_d2h{0};
std::atomic<uint32_t> g_prev_execs{0};
// Serializes the two-window rotation below: two racing fetches would
// otherwise double-count one window's executes in both budgets and zero
// the carry. D2H cadence is per decode tick (milliseconds), so a mutex
// here is noise.
std::mutex g_d2h_window_mu;
constexpr uint64_t kAmbientMaxBytes = 256 * 1024;
// Idle wall of a fetch-sized (128 KiB) round trip, probed at attach next to
// the tiny-payload RttFloor: the charge cap's scale test judges gated FETCH
// walls against this (see probe_transport_floor and charge_sync_wall); the
// universal exemption floor stays tiny-payload. 0 = not probed (the scale
// test then falls back to the tiny floor — tighter, conservative).
std::atomic<uint64_t> g_fetch_floor_ns{0};
// Event-settled execute busy, accumulated for the charge cap's per-execute
// budget. Deliberately SEPARATE from the stats diagnostics: those are
// resettable (vtpu_stats_reset between benchmark phases), and enforcement
// state must never degrade because a monitor zeroed its counters.
std::atomic<uint64_t> g_settles{0};
std::atomic<uint64_t> g_settled_busy_ns{0};
// Rolling window of recent cap-eligible D2H walls (guarded by
// g_d2h_window_mu). On a PROXIED rig (fetch floor >= 10 ms) the scale
// band tracks max(fetch_floor, min of these): a relay storm stretches
// every wall together, and an attach-static band would flip them all to
// charged-in-full exactly when transport misattribution is worst
// (round-5 validation run 11). The min over recent walls is the current
// weather baseline; the budget stays the settled-busy figure either way.
// Local/faithful runtimes (floor ~us) keep the static band, so the
// lying-event smoke case (7c) and direct-attached prod are unaffected.
// Trade, documented: on a lying-event HIGH-RTT relay a saturating 1:1
// tenant's own walls raise the band over itself — dev-rig adversarial
// tightness is traded for correct attribution; prod never takes this
// path.
constexpr int kRecentWalls = 32;
constexpr uint64_t kProxiedFloorNs = 10'000'000;  // 10 ms
uint64_t g_recent_walls[kRecentWalls] = {0};
int g_recent_walls_idx = 0;

// The floor charge_sync_wall actually starts from (before the per-wall 1/16
// clamp): the operator-declared value when set, else the calibrated minimum
// capped at the operator ceiling. Single source for the charge path AND the
// rtt_floor_ns stat, so operators debug the floor that is really applied.
uint64_t base_charge_floor_ns(const Limits& limits) {
  if (limits.charge_floor_ns > 0) return limits.charge_floor_ns;
  if (!limits.charge_floor_auto) return 0;
  uint64_t floor = rtt_floor().floor_ns(tick_ns());
  return floor > limits.charge_floor_max_ns ? limits.charge_floor_max_ns : floor;
}


// Escape hatch for A/B attribution runs: VTPU_DISABLE_SIZE_CACHE=1 restores
// the per-call sizing the cache replaces, so the overhead of the cold path
// can be measured against the cached one on the same binary.
bool size_cache_disabled() {
  static const bool v = [] {
    const char* e = std::getenv("VTPU_DISABLE_SIZE_CACHE");
    return e != nullptr && *e == '1';
  }();
  return v;
}

// ---------------------------------------------------------------- tagged errors

struct VtpuError {
  PJRT_Error_Code code;
  std::string message;
};

std::mutex g_err_mu;
std::unordered_set<void*> g_live_errors;

PJRT_Error* make_error(PJRT_Error_Code code, std::string msg) {
  auto* e = new VtpuError{code, std::move(msg)};
  std::lock_guard<std::mutex> lock(g_err_mu);
  g_live_errors.insert(e);
  return reinterpret_cast<PJRT_Error*>(e);
}

VtpuError* as_vtpu_error(const PJRT_Error* err) {
  void* p = const_cast<PJRT_Error*>(err);
  std::lock_guard<std::mutex> lock(g_err_mu);
  return g_live_errors.count(p) ? reinterpret_cast<VtpuError*>(p) : nullptr;
}

// ---------------------------------------------------------------- global state

struct DeviceState {
  uint64_t used_bytes = 0;
  uint64_t limit_bytes = 0;
  DutyCycleLimiter* limiter = nullptr;
};

struct State {
  Limits limits;
  Region* region = nullptr;
  const PJRT_Api* real = nullptr;
  PJRT_Api wrapped;
  std::mutex mu;
  std::vector<DeviceState> devices;
  std::unordered_map<PJRT_Device*, size_t> device_index;
  // Lock-free mirror of device_index.size() for hot paths (event await)
  // that only need "single chip or not" — fixed after client creation.
  std::atomic<size_t> device_count{0};
  // buffer -> (device index, bytes)
  std::unordered_map<PJRT_Buffer*, std::pair<size_t, uint64_t>> buffers;

  DeviceState& dev(size_t i) {
    if (i >= devices.size()) devices.resize(i + 1);
    auto& d = devices[i];
    if (d.limiter == nullptr) {
      d.limit_bytes = limits.limit_for(i);
      d.limiter = new DutyCycleLimiter(limits.core_limit_percent);
    }
    return d;
  }
};

State& S() {
  static State* s = [] {
    auto* st = new State();
    st->limits = parse_limits_from_env();
    st->region = Region::open(st->limits.region_path, st->limits.task_priority);
    if (st->region) {
      for (size_t i = 0; i < st->limits.hbm_limit_bytes.size(); i++) {
        char name[32];
        std::snprintf(name, sizeof(name), "device-%zu", i);
        st->region->set_device(i, name, st->limits.hbm_limit_bytes[i],
                               st->limits.core_limit_percent);
      }
    }
    VTPU_INFO("init: %zu HBM limits, core=%d%%, policy=%s, region=%s",
              st->limits.hbm_limit_bytes.size(), st->limits.core_limit_percent,
              st->limits.core_policy.c_str(),
              st->limits.region_path.empty() ? "<none>" : st->limits.region_path.c_str());
    return st;
  }();
  return *s;
}

uint64_t dtype_bits(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_PRED:
    case PJRT_Buffer_Type_S8:
    case PJRT_Buffer_Type_U8:
    case PJRT_Buffer_Type_F8E5M2:
    case PJRT_Buffer_Type_F8E4M3FN:
    case PJRT_Buffer_Type_F8E4M3B11FNUZ:
    case PJRT_Buffer_Type_F8E5M2FNUZ:
    case PJRT_Buffer_Type_F8E4M3FNUZ:
      return 8;
    case PJRT_Buffer_Type_S16:
    case PJRT_Buffer_Type_U16:
    case PJRT_Buffer_Type_F16:
    case PJRT_Buffer_Type_BF16:
      return 16;
    case PJRT_Buffer_Type_S32:
    case PJRT_Buffer_Type_U32:
    case PJRT_Buffer_Type_F32:
      return 32;
    case PJRT_Buffer_Type_S64:
    case PJRT_Buffer_Type_U64:
    case PJRT_Buffer_Type_F64:
    case PJRT_Buffer_Type_C64:
      return 64;
    case PJRT_Buffer_Type_C128:
      return 128;
    case PJRT_Buffer_Type_S4:
    case PJRT_Buffer_Type_U4:
      return 4;
    default:
      return 32;
  }
}

uint64_t estimate_bytes(PJRT_Buffer_Type type, const int64_t* dims, size_t n) {
  uint64_t elems = 1;
  for (size_t i = 0; i < n; i++) elems *= (dims[i] > 0 ? (uint64_t)dims[i] : 1);
  uint64_t bits = elems * dtype_bits(type);
  return (bits + 7) / 8;
}

size_t device_index_of(PJRT_Device* device) {
  auto& s = S();
  std::lock_guard<std::mutex> lock(s.mu);
  auto it = s.device_index.find(device);
  if (it != s.device_index.end()) return it->second;
  size_t idx = s.device_index.size();
  s.device_index.emplace(device, idx);
  s.device_count.store(s.device_index.size(), std::memory_order_relaxed);
  return idx;
}

void refresh_device_map(PJRT_Client* client) {
  // Stable device indexes: position in the client's addressable-device list
  // maps 1:1 to TPU_DEVICE_MEMORY_LIMIT_<i> order.
  auto& s = S();
  if (s.real == nullptr || s.real->PJRT_Client_AddressableDevices == nullptr) return;
  PJRT_Client_AddressableDevices_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  args.client = client;
  PJRT_Error* err = s.real->PJRT_Client_AddressableDevices(&args);
  if (err != nullptr) {
    PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, err};
    s.real->PJRT_Error_Destroy(&d);
    return;
  }
  std::lock_guard<std::mutex> lock(s.mu);
  for (size_t i = 0; i < args.num_addressable_devices; i++) {
    s.device_index[args.addressable_devices[i]] = i;
  }
  s.device_count.store(s.device_index.size(), std::memory_order_relaxed);
  VTPU_INFO("mapped %zu addressable devices", args.num_addressable_devices);
}

void destroy_real_error(PJRT_Error* err);
void destroy_event(PJRT_Event* ev);

// Attach-time transport probe: the shim's own tiny upload + read-back,
// waited to transfer completion, on the fresh client — BEFORE any tenant
// work exists. The minimum of 4 round trips seeds the transport floor (see
// RttFloor). Everything goes through s.real directly so the shim's own HBM
// accounting never sees the probe buffers. Cost: two phases of 4 round
// trips each (tiny + 128 KiB payloads) once per attach — µs locally, ~1 s
// on the proxied dev runtime; noise next to attach+compile.
// Await-then-destroy a real-API event (probe helper).
bool await_and_destroy(PJRT_Event* ev) {
  if (ev == nullptr) return true;
  auto& s = S();
  PJRT_Event_Await_Args aw;
  std::memset(&aw, 0, sizeof(aw));
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  bool ok = true;
  if (PJRT_Error* aerr = s.real->PJRT_Event_Await(&aw)) {
    destroy_real_error(aerr);
    ok = false;
  }
  destroy_event(ev);
  return ok;
}

void probe_transport_floor(PJRT_Client* client) {
  auto& s = S();
  if (!s.limits.charge_floor_auto || s.limits.charge_floor_ns > 0) return;
  // Probe ONCE per process, at the FIRST attach: that is the pre-tenant-work
  // moment the un-gameability argument rests on. Re-creating clients must
  // not re-open calibration — probe walls on a later attach would include
  // whatever the tenant queued, the adversarial drift this design removes.
  static std::atomic<bool> probed{false};
  if (probed.exchange(true)) return;
  if (s.real->PJRT_Client_BufferFromHostBuffer == nullptr ||
      s.real->PJRT_Buffer_ToHostBuffer == nullptr ||
      s.real->PJRT_Buffer_Destroy == nullptr ||
      s.real->PJRT_Event_Await == nullptr ||
      s.real->PJRT_Event_Destroy == nullptr) {
    VTPU_WARN("transport floor probe skipped: plugin lacks a required entry "
              "point; full walls will be charged (declare "
              "VTPU_CHARGE_FLOOR_MS on proxied runtimes)");
    return;
  }
  PJRT_Client_AddressableDevices_Args da;
  std::memset(&da, 0, sizeof(da));
  da.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  da.client = client;
  if (PJRT_Error* err = s.real->PJRT_Client_AddressableDevices(&da)) {
    destroy_real_error(err);
    VTPU_WARN("transport floor probe failed listing devices; full walls "
              "will be charged");
    return;
  }
  if (da.num_addressable_devices == 0) return;

  // TWO payloads are probed, for two different consumers:
  //  - tiny (256 B): the universal charge-exemption floor (RttFloor). It
  //    must stay payload-free — it deducts from EVERY sync wall, including
  //    event-await and large/ungated D2H walls that carry no fetch
  //    payload; a payload-sized value here would over-exempt real compute
  //    on lying-event runtimes (r05_6 review finding).
  //  - fetch-sized (128 KiB — the middle of the gated class, which
  //    kAmbientMaxBytes bounds at 256 KiB): the charge cap's scale-test
  //    reference (g_fetch_floor_ns). The cap judges gated FETCH walls,
  //    and on a chunking relay a tiny-payload reference under-measures
  //    their idle cost by the transfer time (round-5 validation run 5:
  //    71 ms tiny floor vs 115 ms idle fetch walls, which parked the
  //    scale test right below typical walls and re-enabled the charging
  //    the cap exists to prevent).
  static float src[32 * 1024] = {0};
  static char dst[sizeof(src)];
  for (int phase = 0; phase < 2; phase++) {
  int64_t dims[1] = {phase == 0 ? 64 : 32 * 1024};
  uint64_t fetch_min = UINT64_MAX;
  for (int i = 0; i < RttFloor::kMinSamples; i++) {
    PJRT_Client_BufferFromHostBuffer_Args ba;
    std::memset(&ba, 0, sizeof(ba));
    ba.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    ba.client = client;
    ba.data = src;
    ba.type = PJRT_Buffer_Type_F32;
    ba.dims = dims;
    ba.num_dims = 1;
    ba.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    ba.device = da.addressable_devices[0];
    if (PJRT_Error* err = s.real->PJRT_Client_BufferFromHostBuffer(&ba)) {
      destroy_real_error(err);
      VTPU_WARN("transport floor probe upload failed (iteration %d); "
                "floor stays at %llu ns", i,
                (unsigned long long)rtt_floor().floor_ns(tick_ns()));
      return;
    }
    // kImmutableUntilTransferCompletes: src (stack) must stay valid until
    // this fires — await it, never just destroy it, or an error return
    // below could free src under an in-flight H2D
    bool ok = await_and_destroy(ba.done_with_host_buffer);
    if (ba.buffer == nullptr) return;
    uint64_t t0 = tick_ns();
    PJRT_Buffer_ToHostBuffer_Args th;
    std::memset(&th, 0, sizeof(th));
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = ba.buffer;
    th.dst = dst;
    th.dst_size = (size_t)dims[0] * sizeof(float);
    if (ok) {
      PJRT_Error* terr = s.real->PJRT_Buffer_ToHostBuffer(&th);
      if (terr != nullptr) {
        destroy_real_error(terr);
        ok = false;
      } else {
        ok = await_and_destroy(th.event);
      }
    }
    uint64_t t1 = tick_ns();
    PJRT_Buffer_Destroy_Args del;
    std::memset(&del, 0, sizeof(del));
    del.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    del.buffer = ba.buffer;
    if (PJRT_Error* derr = s.real->PJRT_Buffer_Destroy(&del)) {
      destroy_real_error(derr);
    }
    if (!ok) {
      VTPU_WARN("transport floor probe round trip failed (iteration %d); "
                "floor stays at %llu ns", i,
                (unsigned long long)rtt_floor().floor_ns(tick_ns()));
      return;
    }
    if (phase == 0) {
      rtt_floor().record(t1 - t0, t1);
    } else if (t1 - t0 < fetch_min) {
      fetch_min = t1 - t0;
    }
  }
  if (phase == 1 && fetch_min != UINT64_MAX) {
    // Same operator ceiling the tiny floor gets in base_charge_floor_ns: an
    // attach into a congested relay must not inflate the cap's eligibility
    // band for the process lifetime (the probe is attach-static).
    if (fetch_min > s.limits.charge_floor_max_ns) {
      fetch_min = s.limits.charge_floor_max_ns;
    }
    g_fetch_floor_ns.store(fetch_min, std::memory_order_relaxed);
  }
  }
  VTPU_INFO("transport floors probed: tiny %llu ns, fetch %llu ns",
            (unsigned long long)rtt_floor().floor_ns(tick_ns()),
            (unsigned long long)g_fetch_floor_ns.load(std::memory_order_relaxed));
}

uint64_t buffer_device_size(PJRT_Buffer* buffer) {
  auto& s = S();
  if (s.real->PJRT_Buffer_OnDeviceSizeInBytes == nullptr) return 0;
  stats().size_rpcs.fetch_add(1, std::memory_order_relaxed);
  ScopedNs timer(stats().size_rpc_ns);
  PJRT_Buffer_OnDeviceSizeInBytes_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_OnDeviceSizeInBytes_Args_STRUCT_SIZE;
  args.buffer = buffer;
  PJRT_Error* err = s.real->PJRT_Buffer_OnDeviceSizeInBytes(&args);
  if (err != nullptr) {
    PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, err};
    s.real->PJRT_Error_Destroy(&d);
    return 0;
  }
  return args.on_device_size_in_bytes;
}

// Per-executable output metadata. XLA executables have static output shapes,
// so the on-device sizes observed on the first execute hold for every later
// one — caching them removes num_outputs per-execute PJRT round-trips (each
// potentially a remote RPC that blocks until the output buffer is defined,
// serializing an otherwise-async dispatch).
struct ExecMeta {
  size_t num_outputs = 0;
  bool sized = false;
  std::vector<uint64_t> out_sizes;  // per output index; valid when sized
};

std::mutex g_execmeta_mu;
std::unordered_map<PJRT_LoadedExecutable*, ExecMeta> g_execmeta;

size_t executable_num_outputs(PJRT_LoadedExecutable* loaded) {
  auto& s = S();
  {
    // Hot path: one lookup instead of three PJRT round-trips per execute.
    std::lock_guard<std::mutex> lock(g_execmeta_mu);
    auto it = g_execmeta.find(loaded);
    if (it != g_execmeta.end()) return it->second.num_outputs;
  }
  ScopedNs timer(stats().numout_rpc_ns);
  if (s.real->PJRT_LoadedExecutable_GetExecutable == nullptr ||
      s.real->PJRT_Executable_NumOutputs == nullptr) {
    return 0;
  }
  PJRT_LoadedExecutable_GetExecutable_Args ge;
  std::memset(&ge, 0, sizeof(ge));
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.loaded_executable = loaded;
  if (PJRT_Error* err = s.real->PJRT_LoadedExecutable_GetExecutable(&ge)) {
    PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, err};
    s.real->PJRT_Error_Destroy(&d);
    return 0;
  }
  PJRT_Executable_NumOutputs_Args no;
  std::memset(&no, 0, sizeof(no));
  no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  no.executable = ge.executable;
  size_t n = 0;
  if (PJRT_Error* err = s.real->PJRT_Executable_NumOutputs(&no)) {
    PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, err};
    s.real->PJRT_Error_Destroy(&d);
  } else {
    n = no.num_outputs;
  }
  if (s.real->PJRT_Executable_Destroy != nullptr && ge.executable != nullptr) {
    PJRT_Executable_Destroy_Args ed;
    std::memset(&ed, 0, sizeof(ed));
    ed.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
    ed.executable = ge.executable;
    if (PJRT_Error* err = s.real->PJRT_Executable_Destroy(&ed)) {
      PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, err};
      s.real->PJRT_Error_Destroy(&d);
    }
  }
  {
    std::lock_guard<std::mutex> lock(g_execmeta_mu);
    g_execmeta[loaded].num_outputs = n;
  }
  return n;
}

// Cached output sizes for an executable, or empty when not yet observed
// (first execute) or when the A/B flag disables the cache.
std::vector<uint64_t> cached_output_sizes(PJRT_LoadedExecutable* loaded) {
  if (size_cache_disabled()) return {};
  std::lock_guard<std::mutex> lock(g_execmeta_mu);
  auto it = g_execmeta.find(loaded);
  if (it == g_execmeta.end() || !it->second.sized) return {};
  return it->second.out_sizes;
}

void store_output_sizes(PJRT_LoadedExecutable* loaded,
                        std::vector<uint64_t> sizes) {
  std::lock_guard<std::mutex> lock(g_execmeta_mu);
  auto& meta = g_execmeta[loaded];
  meta.out_sizes = std::move(sizes);
  meta.sized = true;
}

void account_alloc(PJRT_Buffer* buffer, size_t dev_idx, uint64_t bytes) {
  auto& s = S();
  {
    std::lock_guard<std::mutex> lock(s.mu);
    s.dev(dev_idx).used_bytes += bytes;
    s.buffers[buffer] = {dev_idx, bytes};
  }
  if (s.region) {
    ScopedNs timer(stats().region_ns);
    s.region->add_used(dev_idx, (int64_t)bytes);
  }
  VTPU_TRACE("alloc dev%zu %lu bytes (used=%lu)", dev_idx, (unsigned long)bytes,
             (unsigned long)s.devices[dev_idx].used_bytes);
}

// Account one execute output row in a single pass: one state lock for all
// buffers and ONE shared-region write for the row total, instead of a lock +
// region write per buffer.
void account_output_row(PJRT_Buffer** outs, const uint64_t* sizes, size_t n,
                        size_t dev_idx) {
  auto& s = S();
  uint64_t total = 0;
  {
    std::lock_guard<std::mutex> lock(s.mu);
    auto& dev = s.dev(dev_idx);
    for (size_t o = 0; o < n; o++) {
      if (outs[o] == nullptr) continue;
      dev.used_bytes += sizes[o];
      s.buffers[outs[o]] = {dev_idx, sizes[o]};
      total += sizes[o];
    }
  }
  if (total && s.region) {
    ScopedNs timer(stats().region_ns);
    s.region->add_used(dev_idx, (int64_t)total);
  }
}

// ---------------------------------------------------------------- wrappers

void wrapped_error_destroy(PJRT_Error_Destroy_Args* args) {
  if (auto* e = as_vtpu_error(args->error)) {
    {
      std::lock_guard<std::mutex> lock(g_err_mu);
      g_live_errors.erase(args->error);
    }
    delete e;
    return;
  }
  S().real->PJRT_Error_Destroy(args);
}

void wrapped_error_message(PJRT_Error_Message_Args* args) {
  if (auto* e = as_vtpu_error(args->error)) {
    args->message = e->message.c_str();
    args->message_size = e->message.size();
    return;
  }
  S().real->PJRT_Error_Message(args);
}

PJRT_Error* wrapped_error_getcode(PJRT_Error_GetCode_Args* args) {
  if (auto* e = as_vtpu_error(args->error)) {
    args->code = e->code;
    return nullptr;
  }
  return S().real->PJRT_Error_GetCode(args);
}

uint64_t mono_ms() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000ull + (uint64_t)ts.tv_nsec / 1000000ull;
}

void destroy_real_error(PJRT_Error* err) {
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = err;
  S().real->PJRT_Error_Destroy(&d);
}

void destroy_event(PJRT_Event* ev) {
  auto& s = S();
  if (ev == nullptr || s.real->PJRT_Event_Destroy == nullptr) return;
  PJRT_Event_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  if (PJRT_Error* derr = s.real->PJRT_Event_Destroy(&d)) {
    destroy_real_error(derr);
  }
}

PJRT_Error_Code real_error_code(PJRT_Error* err) {
  PJRT_Error_GetCode_Args code_args;
  std::memset(&code_args, 0, sizeof(code_args));
  code_args.struct_size = PJRT_Error_GetCode_Args_STRUCT_SIZE;
  code_args.error = err;
  PJRT_Error* code_err = S().real->PJRT_Error_GetCode(&code_args);
  if (code_err == nullptr) return code_args.code;
  destroy_real_error(code_err);
  return PJRT_Error_Code_UNKNOWN;
}

PJRT_Error* wrapped_client_create(PJRT_Client_Create_Args* args) {
  auto& s = S();
  // Attach queueing (docs/multitenancy.md): on an exclusive-attach runtime a
  // second tenant's create fails busy-class while another tenant holds the
  // chip. With VTPU_ATTACH_WAIT_MS > 0 the tenant queues here with backoff —
  // time-multiplexed tenancy at client granularity — instead of failing (and
  // crash-looping its pod). On concurrent-attach runtimes the first create
  // succeeds and this loop runs exactly once.
  const uint64_t wait_ms = s.limits.attach_wait_ms;
  const uint64_t deadline = wait_ms ? mono_ms() + wait_ms : 0;
  uint64_t backoff_ms = 50;
  for (;;) {
    PJRT_Error* err = s.real->PJRT_Client_Create(args);
    if (err == nullptr) {
      if (args->client != nullptr) {
        refresh_device_map(args->client);
        probe_transport_floor(args->client);
        // Active attestation (calib.h): compile + run the known-duration
        // probe through the REAL table on the fresh client. dev(0)'s
        // limiter receives the oracle's self-charged (unpaced) probe busy.
        DutyCycleLimiter* limiter0;
        {
          std::lock_guard<std::mutex> lock(s.mu);
          limiter0 = s.dev(0).limiter;
        }
        calib::calibrate_at_attach(s.real, args->client, s.region, limiter0);
      }
      return nullptr;
    }
    PJRT_Error_Code code = real_error_code(err);
    const bool busy = code == PJRT_Error_Code_UNAVAILABLE ||
                      code == PJRT_Error_Code_ABORTED ||
                      code == PJRT_Error_Code_RESOURCE_EXHAUSTED;
    if (busy && wait_ms > 0) {
      const uint64_t now = mono_ms();
      if (now < deadline) {
        destroy_real_error(err);
        const uint64_t remaining = deadline - now;
        const uint64_t sleep_ms = backoff_ms < remaining ? backoff_ms : remaining;
        VTPU_INFO("chip busy on attach (code %d); queueing, retry in %lu ms",
                  (int)code, (unsigned long)sleep_ms);
        usleep((useconds_t)(sleep_ms * 1000));
        backoff_ms = backoff_ms * 2 < 1000 ? backoff_ms * 2 : 1000;
        continue;
      }
      // Deadline exhausted on a merely-HELD chip: surface the error to the
      // tenant, but this is contention, not infrastructure — a fatal-health
      // event here would bench a healthy shared chip for every tenant.
      VTPU_WARN("attach wait deadline (%lu ms) exceeded; chip still held "
                "(code %d)", (unsigned long)wait_ms, (int)code);
      return err;
    }
    // Only infrastructure-class failures are health events; app-caused ones
    // (bad options, double init -> INVALID_ARGUMENT/FAILED_PRECONDITION/...)
    // must not bench a shared chip for every tenant (reference rm/health.go
    // skipping application-caused XIDs 13/31/43/45/68).
    switch (code) {
      case PJRT_Error_Code_UNKNOWN:
      case PJRT_Error_Code_DEADLINE_EXCEEDED:
      case PJRT_Error_Code_INTERNAL:
      case PJRT_Error_Code_UNAVAILABLE:
      case PJRT_Error_Code_DATA_LOSS:
        // A wedged chip shows up here first (the XID analog).
        report_fatal_health("PJRT_Client_Create failed (infrastructure)");
        break;
      default:
        VTPU_WARN("PJRT_Client_Create failed with app-level code %d", (int)code);
        break;
    }
    return err;
  }
}

// Reserve est bytes on dev_idx ahead of a real allocation (under the lock,
// BEFORE the real call, so two racing threads can't both pass the check and
// jointly blow the cap). Returns a tagged RESOURCE_EXHAUSTED error when the
// cap would be exceeded and oversubscription is off; else sets *reserved.
PJRT_Error* precheck_alloc(size_t dev_idx, uint64_t est, bool* reserved) {
  auto& s = S();
  *reserved = false;
  if (!s.limits.mem_enforced()) return nullptr;
  std::unique_lock<std::mutex> lock(s.mu);
  auto& dev = s.dev(dev_idx);
  if (dev.limit_bytes > 0 && dev.used_bytes + est > dev.limit_bytes) {
    uint64_t used = dev.used_bytes, limit = dev.limit_bytes;
    lock.unlock();
    if (!s.limits.oversubscribe) {
      char msg[256];
      std::snprintf(msg, sizeof(msg),
                    "vtpu: HBM limit exceeded on device %zu: "
                    "used %lu + request %lu > limit %lu bytes "
                    "(TPU_DEVICE_MEMORY_LIMIT_%zu)",
                    dev_idx, (unsigned long)used, (unsigned long)est,
                    (unsigned long)limit, dev_idx);
      VTPU_WARN("%s", msg);
      return make_error(PJRT_Error_Code_RESOURCE_EXHAUSTED, msg);
    }
    VTPU_WARN("oversubscribe: dev%zu exceeding cap (used=%lu est=%lu limit=%lu)",
              dev_idx, (unsigned long)used, (unsigned long)est,
              (unsigned long)limit);
  } else {
    dev.used_bytes += est;
    *reserved = true;
  }
  return nullptr;
}

void unreserve(size_t dev_idx, uint64_t est) {
  auto& s = S();
  std::lock_guard<std::mutex> lock(s.mu);
  auto& dev = s.dev(dev_idx);
  dev.used_bytes = dev.used_bytes >= est ? dev.used_bytes - est : 0;
}

// Real on-device sizes observed per (dtype, dims) signature. Serving traffic
// repeats a handful of upload shapes forever; after the first observation the
// settle step needs no PJRT round-trip. Keyed by FNV-1a of the logical shape —
// on one plugin the physical layout (and so the size) is a function of it.
std::mutex g_upsize_mu;
std::unordered_map<uint64_t, uint64_t> g_upsize_cache;

uint64_t shape_sig(PJRT_Buffer_Type type, const int64_t* dims, size_t n) {
  uint64_t h = 1469598103934665603ull;
  auto mix = [&h](uint64_t v) {
    h ^= v;
    h *= 1099511628211ull;
  };
  mix((uint64_t)type + 1);
  mix(n);
  for (size_t i = 0; i < n; i++) mix((uint64_t)dims[i]);
  return h;
}

// Settle a successful allocation: replace the pre-charged estimate by the
// buffer's real on-device size and record the buffer for Destroy accounting.
// `sig` (when nonzero) keys the observed-size cache; 0 queries the plugin —
// unless `trust_est` says est already IS a real on-device size (copies).
void settle_alloc(PJRT_Buffer* buffer, size_t dev_idx, uint64_t est,
                  bool reserved, uint64_t sig = 0, bool trust_est = false) {
  if (reserved) unreserve(dev_idx, est);
  if (trust_est && est != 0) {
    account_alloc(buffer, dev_idx, est);
    return;
  }
  if (sig != 0 && !size_cache_disabled()) {
    uint64_t cached = 0;
    bool hit = false;
    {
      std::lock_guard<std::mutex> lock(g_upsize_mu);
      auto it = g_upsize_cache.find(sig);
      if (it != g_upsize_cache.end()) {
        cached = it->second;
        hit = true;
      }
    }
    if (hit) {
      stats().size_cache_hits.fetch_add(1, std::memory_order_relaxed);
      account_alloc(buffer, dev_idx, cached ? cached : est);
      return;
    }
  }
  stats().size_cache_misses.fetch_add(1, std::memory_order_relaxed);
  uint64_t real_size = buffer_device_size(buffer);
  if (sig != 0 && real_size != 0) {
    std::lock_guard<std::mutex> lock(g_upsize_mu);
    if (g_upsize_cache.size() > 65536) g_upsize_cache.clear();  // unbounded guard
    g_upsize_cache[sig] = real_size;
  }
  account_alloc(buffer, dev_idx, real_size ? real_size : est);
}

// Host memory spaces (pinned_host / unpinned_host) live in RAM, not HBM:
// allocations there must never be charged against — or blocked by — a chip's
// cap. (JAX host offloading is exactly how a tenant gets back UNDER its cap.)
bool memory_is_host(PJRT_Memory* mem);
// Post-hoc cap settlement for allocations whose destination device is only
// known from the resulting buffer.
PJRT_Error* settle_or_reject(PJRT_Buffer** buffer, uint64_t est, uint64_t sig,
                             bool trust_est = false);

// Every branch routes the real call through this so the upload timing can
// never diverge between them.
PJRT_Error* timed_real_upload(PJRT_Client_BufferFromHostBuffer_Args* args) {
  ScopedNs real_timer(stats().upload_real_ns);
  return S().real->PJRT_Client_BufferFromHostBuffer(args);
}

PJRT_Error* wrapped_buffer_from_host(PJRT_Client_BufferFromHostBuffer_Args* args) {
  stats().uploads.fetch_add(1, std::memory_order_relaxed);
  ScopedNs total_timer(stats().upload_ns);
  uint64_t est = estimate_bytes(args->type, args->dims, args->num_dims);
  // A custom device_layout changes the physical size of the same logical
  // shape; only the default (nullptr) layout may share the size cache.
  bool custom_layout =
      offsetof(PJRT_Client_BufferFromHostBuffer_Args, device_layout) +
              sizeof(void*) <=
          args->struct_size &&
      args->device_layout != nullptr;
  uint64_t sig =
      custom_layout ? 0 : shape_sig(args->type, args->dims, args->num_dims);
  if (args->memory != nullptr) {
    // PJRT gives `memory` precedence over `device` when both are set: host
    // spaces bypass HBM accounting; device spaces settle post-hoc from the
    // resulting buffer's device.
    if (memory_is_host(args->memory)) {
      return timed_real_upload(args);
    }
    PJRT_Error* err = timed_real_upload(args);
    if (err != nullptr || args->buffer == nullptr) return err;
    return settle_or_reject(&args->buffer, est, sig);
  }
  size_t dev_idx = args->device ? device_index_of(args->device) : 0;
  bool reserved = false;
  if (PJRT_Error* verr = precheck_alloc(dev_idx, est, &reserved)) return verr;
  PJRT_Error* err = timed_real_upload(args);
  if (err != nullptr || args->buffer == nullptr) {
    if (reserved) unreserve(dev_idx, est);
    return err;
  }
  settle_alloc(args->buffer, dev_idx, est, reserved, sig);
  return nullptr;
}

// PJRT_Memory handles are stable for the client's lifetime, so the kind
// lookup (a potential remote RPC on every upload) is cached per handle.
std::mutex g_memkind_mu;
std::unordered_map<PJRT_Memory*, bool> g_memkind_cache;

bool memory_is_host(PJRT_Memory* mem) {
  auto& s = S();
  if (mem == nullptr || s.wrapped.PJRT_Memory_Kind == nullptr) return false;
  {
    std::lock_guard<std::mutex> lock(g_memkind_mu);
    auto it = g_memkind_cache.find(mem);
    if (it != g_memkind_cache.end()) return it->second;
  }
  stats().memkind_rpcs.fetch_add(1, std::memory_order_relaxed);
  bool is_host = false;
  {
    ScopedNs timer(stats().memkind_rpc_ns);
    PJRT_Memory_Kind_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Memory_Kind_Args_STRUCT_SIZE;
    args.memory = mem;
    if (PJRT_Error* err = s.real->PJRT_Memory_Kind(&args)) {
      PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, err};
      s.real->PJRT_Error_Destroy(&d);
      return false;  // not cached: a failed lookup may succeed later
    }
    std::string kind(args.kind ? args.kind : "", args.kind_size);
    is_host = kind.find("host") != std::string::npos;
  }
  {
    std::lock_guard<std::mutex> lock(g_memkind_mu);
    g_memkind_cache[mem] = is_host;
  }
  return is_host;
}

// Over-cap -> destroy the fresh buffer and return the tagged error, so the
// tenant never holds memory past its cap.
PJRT_Error* settle_or_reject(PJRT_Buffer** buffer, uint64_t est, uint64_t sig,
                             bool trust_est) {
  auto& s = S();
  size_t dev_idx = 0;
  if (s.wrapped.PJRT_Buffer_Device != nullptr) {
    PJRT_Buffer_Device_Args dargs;
    std::memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Device_Args_STRUCT_SIZE;
    dargs.buffer = *buffer;
    if (PJRT_Error* derr = s.real->PJRT_Buffer_Device(&dargs)) {
      PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, derr};
      s.real->PJRT_Error_Destroy(&d);
    } else if (dargs.device != nullptr) {
      dev_idx = device_index_of(dargs.device);
    }
  }
  bool reserved = false;
  if (PJRT_Error* verr = precheck_alloc(dev_idx, est, &reserved)) {
    PJRT_Buffer_Destroy_Args del;
    std::memset(&del, 0, sizeof(del));
    del.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    del.buffer = *buffer;
    if (PJRT_Error* kerr = s.real->PJRT_Buffer_Destroy(&del)) {
      PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, kerr};
      s.real->PJRT_Error_Destroy(&d);
    }
    *buffer = nullptr;
    return verr;
  }
  settle_alloc(*buffer, dev_idx, est, reserved, sig, trust_est);
  return nullptr;
}

PJRT_Error* wrapped_create_uninitialized(
    PJRT_Client_CreateUninitializedBuffer_Args* args) {
  auto& s = S();
  uint64_t est =
      estimate_bytes(args->shape_element_type, args->shape_dims, args->shape_num_dims);
  // Same rule as BufferFromHostBuffer: a custom layout opts out of the
  // shared shape-size cache.
  uint64_t sig =
      args->shape_layout != nullptr
          ? 0
          : shape_sig(args->shape_element_type, args->shape_dims,
                      args->shape_num_dims);
  if (args->memory != nullptr) {
    // PJRT gives `memory` precedence over `device` when both are set: host
    // spaces bypass HBM accounting entirely; device spaces settle post-hoc
    // from the resulting buffer's device.
    if (memory_is_host(args->memory)) {
      return s.real->PJRT_Client_CreateUninitializedBuffer(args);
    }
    PJRT_Error* err = s.real->PJRT_Client_CreateUninitializedBuffer(args);
    if (err != nullptr || args->buffer == nullptr) return err;
    return settle_or_reject(&args->buffer, est, sig);
  }
  size_t dev_idx = args->device ? device_index_of(args->device) : 0;
  bool reserved = false;
  if (PJRT_Error* verr = precheck_alloc(dev_idx, est, &reserved)) return verr;
  PJRT_Error* err = s.real->PJRT_Client_CreateUninitializedBuffer(args);
  if (err != nullptr || args->buffer == nullptr) {
    if (reserved) unreserve(dev_idx, est);
    return err;
  }
  settle_alloc(args->buffer, dev_idx, est, reserved, sig);
  return nullptr;
}

PJRT_Error* wrapped_copy_to_device(PJRT_Buffer_CopyToDevice_Args* args) {
  // Device-to-device copies allocate on the destination chip; without this
  // hook a tenant could sidestep its cap by staging through another device
  // (the reference's cuMemcpyPeer-class paths are hooked the same way).
  auto& s = S();
  size_t dev_idx = args->dst_device ? device_index_of(args->dst_device) : 0;
  uint64_t est = buffer_device_size(args->buffer);  // dst ≈ src size
  bool reserved = false;
  if (PJRT_Error* verr = precheck_alloc(dev_idx, est, &reserved)) return verr;
  PJRT_Error* err = s.real->PJRT_Buffer_CopyToDevice(args);
  if (err != nullptr || args->dst_buffer == nullptr) {
    if (reserved) unreserve(dev_idx, est);
    return err;
  }
  // est came from the source's real on-device size; the copy has the same
  // shape on the same plugin, so settle without another size round-trip.
  if (reserved) unreserve(dev_idx, est);
  account_alloc(args->dst_buffer, dev_idx, est);
  return nullptr;
}

PJRT_Error* wrapped_copy_to_memory(PJRT_Buffer_CopyToMemory_Args* args) {
  auto& s = S();
  // Host-space destination (JAX offloading): RAM, not HBM — never charged,
  // never blocked.
  if (memory_is_host(args->dst_memory)) {
    return s.real->PJRT_Buffer_CopyToMemory(args);
  }
  uint64_t est = buffer_device_size(args->buffer);
  PJRT_Error* err = s.real->PJRT_Buffer_CopyToMemory(args);
  if (err != nullptr || args->dst_buffer == nullptr) return err;
  // est here IS a real on-device size (same plugin, same shape): no re-query.
  return settle_or_reject(&args->dst_buffer, est, 0, /*trust_est=*/true);
}

// Charge a wall interval the process spent blocked on the runtime to the
// device's duty-cycle limiter (union accounting inside the limiter prevents
// double charges where faithful completion events already paid). A
// transport floor is deducted first: over a proxied plugin every
// completion-coupled wall carries the dispatch RTT, which is transport,
// not chip busy. The floor is the operator-declared VTPU_CHARGE_FLOOR_MS
// when set, else the self-calibrated small-upload minimum (RttFloor) — so
// the core knob works out of the box on proxied runtimes, like the
// reference's SM limit does locally.
void charge_sync_wall(size_t dev_idx, uint64_t start_ns, uint64_t end_ns,
                      int own_pending_execs = -1) {
  auto& s = S();
  if (!s.limits.core_enforced() && s.region == nullptr) return;
  DutyCycleLimiter* limiter;
  {
    std::lock_guard<std::mutex> lock(s.mu);
    limiter = s.dev(dev_idx).limiter;
  }
  if (calib::events_attested_faithful()) {
    // Live-verified faithful events (calib.h): completion-event settles are
    // the absolute busy reference, so this wall is transport plus busy the
    // settle path already charged — charging it would rebuild the
    // compensator tower the attestation dissolves. No floor, no band, no
    // cap, no charge; a runtime that later fails re-attestation is demoted
    // and falls back to the full tower below. Counted for every skipped
    // wall (gated or not), so the artifact audit can reconcile
    // attested-mode runs the same way the gate/outcome counters do.
    stats().d2h_attested.fetch_add(1, std::memory_order_relaxed);
    if (s.region) {
      s.region->set_core_util(dev_idx,
                              limiter->current_util_percent(tick_ns()));
    }
    return;
  }
  uint64_t floor = base_charge_floor_ns(s.limits);
  const uint64_t wall_ns = end_ns > start_ns ? end_ns - start_ns : 0;
  if (s.limits.charge_floor_ns == 0 && floor > 0) {
    // Bound the gameable surface: the auto floor never exempts more than
    // 15/16 of a wall, so a tenant that inflated its own calibration still
    // pays 1/16 of observed busy (see RttFloor adversarial notes). An
    // operator-DECLARED floor is trusted in full.
    uint64_t max_exempt = wall_ns - wall_ns / 16;
    if (floor > max_exempt) floor = max_exempt;
  }
  start_ns += floor;
  // Own-work charge cap (r5, see RttFloor AMBIENT notes): when the caller
  // PROVES how many of its own executes can be hiding in this wall
  // (own_pending_execs >= 0 — only the gated D2H paths claim this) AND the
  // wall is transport-scale for its class (wall <= 2x the FETCH-SIZED
  // probed idle wall, g_fetch_floor_ns — the gated class moves payloads,
  // and judging payload walls against the tiny-payload floor parked the
  // threshold right below typical idle fetch walls
  // [round-5 validation runs 4/5: tiny floor 71-80 ms vs idle fetch walls
  // 115-135 ms], so transport-shaped walls charged in full), the charge
  // is capped at that many executes'
  // device-time estimate (the limiter's completion-event-fed EMA) plus
  // copy slack. Relay-queueing jitter above the floor is transport, not
  // duty: a MIN-based floor can never absorb it, and round-5 validation run 1
  // measured it pacing tenants at 0.2% true duty into 20-40 s admit waits.
  // The scale test keeps lying-event runtimes honest: there a cycle's real
  // compute also lands in the D2H wall (smoke 7c), but with local
  // transport the floor is ~us, any real compute dwarfs it, and the wall
  // charges in full. It also bounds the gaming surface: a 1:1
  // execute-fetch adversary can hide at most one floor per RTT-serialized
  // cycle, i.e. < 1/2 duty in the worst case, only on lying-event
  // high-RTT relays — and a tenant pushing real compute past its quota
  // pushes its walls past 2x floor and is charged in full. On
  // direct-attached runtimes the cap never engages. Ungated walls
  // (bursts of many executes per fetch — the core-share proportionality
  // case) are charged in full as before.
  uint64_t fetch_floor = g_fetch_floor_ns.load(std::memory_order_relaxed);
  if (fetch_floor == 0) fetch_floor = floor;  // probe absent: conservative
  uint64_t band_ref = fetch_floor;
  if (own_pending_execs >= 0 && fetch_floor >= kProxiedFloorNs &&
      wall_ns > 0) {
    // Proxied rig: the band reference tracks current weather (see the
    // g_recent_walls notes). Record this wall, then take the rolling min.
    std::lock_guard<std::mutex> wlock(g_d2h_window_mu);
    g_recent_walls[g_recent_walls_idx] = wall_ns;
    g_recent_walls_idx = (g_recent_walls_idx + 1) % kRecentWalls;
    uint64_t vals[kRecentWalls];
    int have = 0;
    for (int i = 0; i < kRecentWalls; i++) {
      if (g_recent_walls[i] > 0) vals[have++] = g_recent_walls[i];
    }
    if (have >= 8) {
      // Low percentile rather than strict min: one anomalously fast wall
      // (runtime-prefetched data, event already ready) must not collapse
      // the band back to the static floor for 32 walls mid-storm.
      int k = have / 8;
      std::nth_element(vals, vals + k, vals + have);
      uint64_t weather = vals[k];
      if (weather > band_ref) band_ref = weather;
      // Hard ceiling: the dynamic band restores the adversarial bound the
      // static test had — a lying-event tenant whose compute stretches its
      // own walls past 4x the probed idle fetch wall fails the band and
      // charges in full, so per-cycle hiding stays bounded instead of the
      // band tracking the adversary's own walls without limit.
      if (band_ref > 4 * fetch_floor) band_ref = 4 * fetch_floor;
    }
  }
  if (own_pending_execs >= 0) {
    if (end_ns <= start_ns) {
      // the floor absorbed the whole wall: nothing to cap, nothing charged
      stats().d2h_floored.fetch_add(1, std::memory_order_relaxed);
    } else if (floor > 0 && wall_ns <= 2 * band_ref) {
      constexpr uint64_t kD2hCopySlackNs = 500'000;  // small copy+sync
      // The per-execute budget is the EVENT-SETTLED busy average, not the
      // limiter's admit EMA: the admit EMA is fed by settle_interval's
      // submit->ready walls, which over a proxied runtime carry transport
      // (round-5 validation audit: admit-EMA-based caps still charged
      // 10-17 ms per capped wall against 0.21 ms/execute event-settled
      // busy — a ~10x overcharge that re-created the admit waits the cap
      // exists to remove). Event-settled busy is device truth on faithful
      // runtimes; on eager-event local runtimes it underestimates, but
      // there the scale test above never lets the cap engage (floor ~us).
      uint64_t settles = g_settles.load(std::memory_order_relaxed);
      uint64_t avg_settle_ns =
          settles > 0
              ? g_settled_busy_ns.load(std::memory_order_relaxed) / settles
              : limiter->estimate_ns();
      uint64_t cap = (uint64_t)own_pending_execs * avg_settle_ns
                     + kD2hCopySlackNs;
      if (end_ns > start_ns + cap) end_ns = start_ns + cap;
      stats().d2h_capped.fetch_add(1, std::memory_order_relaxed);
    } else {
      // charged in full: the scale test failed, or floor==0 (direct
      // runtime / probe skipped) where the cap never engages by design
      stats().d2h_uncapped.fetch_add(1, std::memory_order_relaxed);
    }
  }
  if (end_ns > start_ns) {
    stats().sync_charged_ns.fetch_add(end_ns - start_ns,
                                      std::memory_order_relaxed);
    limiter->charge_interval(start_ns, end_ns);
  }
  // refresh the monitor's view even when the floor exempted this wall: the
  // util must DECAY to zero on a floored-idle tenant, not freeze at the
  // last pre-floor reading
  if (s.region) {
    s.region->set_core_util(dev_idx, limiter->current_util_percent(tick_ns()));
  }
}

PJRT_Error* wrapped_event_await(PJRT_Event_Await_Args* args) {
  auto& st = stats();
  st.await_calls.fetch_add(1, std::memory_order_relaxed);
  uint64_t t0 = tick_ns();
  PJRT_Error* err = S().real->PJRT_Event_Await(args);
  uint64_t t1 = tick_ns();
  st.await_ns.fetch_add(t1 - t0, std::memory_order_relaxed);
  // An event alone does not identify its device; charge chip 0 — exact for
  // the single-chip containers vTPU shares. On a multi-chip assignment the
  // owning chip is unknowable here, so skip entirely: charging chip 0 for
  // waits on chips 1..N would over-throttle it while the busy chip goes
  // uncharged. Those assignments get attribution from the per-buffer D2H
  // path and per-device execute completion events instead.
  if (S().device_count.load(std::memory_order_relaxed) <= 1) {
    charge_sync_wall(0, t0, t1);
  }
  return err;
}

struct D2hCtx {
  size_t dev_idx;
  uint64_t start_ns;
  bool cap_ok;
  uint32_t pending_total;
};

void d2h_done_cb(PJRT_Error* error, void* user_arg) {
  auto* ctx = static_cast<D2hCtx*>(user_arg);
  uint64_t now = tick_ns();
  g_d2h_inflight.fetch_sub(1, std::memory_order_relaxed);
  if (error != nullptr) {
    stats().d2h_errors.fetch_add(1, std::memory_order_relaxed);
  }
  stats().tohost_ns.fetch_add(now - ctx->start_ns, std::memory_order_relaxed);
  charge_sync_wall(ctx->dev_idx, ctx->start_ns, now,
                   ctx->cap_ok ? (int)ctx->pending_total : -1);
  if (error != nullptr) {
    PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, error};
    S().real->PJRT_Error_Destroy(&d);
  }
  delete ctx;
}

PJRT_Error* wrapped_to_host(PJRT_Buffer_ToHostBuffer_Args* args) {
  auto& s = S();
  auto& st = stats();
  st.tohost_calls.fetch_add(1, std::memory_order_relaxed);
  size_t dev_idx = 0;
  uint64_t src_bytes = UINT64_MAX;  // unknown size fails the ambient gate
  {
    std::lock_guard<std::mutex> lock(s.mu);
    auto it = s.buffers.find(args->src);
    if (it != s.buffers.end()) {
      dev_idx = it->second.first;
      src_bytes = it->second.second;
    }
  }
  // Charge-cap gate (see RttFloor AMBIENT notes and charge_sync_wall):
  // eligibility is decided at submit — no other own D2H in flight, an
  // untainted predecessor, small transfer — so the wall's hidden own
  // compute is bounded by the KNOWN number of executes submitted since the
  // previous D2H, and the charge can be capped at that many device-time
  // estimates. A serving TTFT fetch typically follows several executes
  // (prefill + cache install + first decode), so the cap scales with the
  // count rather than requiring <=1; the fetch-floor scale test in
  // charge_sync_wall bounds what a burst could hide regardless.
  uint32_t pending_total;
  {
    std::lock_guard<std::mutex> wlock(g_d2h_window_mu);
    uint32_t execs_now =
        g_execs_since_d2h.exchange(0, std::memory_order_relaxed);
    uint32_t execs_prev =
        g_prev_execs.exchange(execs_now, std::memory_order_relaxed);
    pending_total = execs_now + execs_prev;
  }
  // The gate state is process-global: on a multi-chip assignment one
  // chip's executes would inflate another chip's cap budget (and its
  // in-flight D2H would veto the cap for unrelated chips), so the cap —
  // like the event-await wall charge above — only claims single-chip
  // assignments, the case vTPU containers actually run.
  bool solo_inflight = g_d2h_inflight.fetch_add(1, std::memory_order_relaxed) == 0;
  bool size_ok = src_bytes <= kAmbientMaxBytes;
  bool single_chip = s.device_count.load(std::memory_order_relaxed) <= 1;
  bool cap_ok = solo_inflight && size_ok && single_chip;
  if (!solo_inflight) {
    st.d2h_gate_inflight.fetch_add(1, std::memory_order_relaxed);
  } else if (!size_ok) {
    st.d2h_gate_size.fetch_add(1, std::memory_order_relaxed);
  } else if (!single_chip) {
    st.d2h_gate_multichip.fetch_add(1, std::memory_order_relaxed);
  }
  uint64_t t0 = tick_ns();
  PJRT_Error* err = s.real->PJRT_Buffer_ToHostBuffer(args);
  uint64_t t1 = tick_ns();
  if (err != nullptr) {
    g_d2h_inflight.fetch_sub(1, std::memory_order_relaxed);
    st.d2h_errors.fetch_add(1, std::memory_order_relaxed);
    return err;
  }
  // The D2H completion EVENT is the one signal even eager-event runtimes
  // must keep honest — the caller's bytes have to actually arrive. Observe
  // it WITHOUT consuming and charge [call, ready]; if there is no event,
  // the call itself was synchronous. Piggybacking on the caller-owned event
  // assumes PJRT_Event_OnReady supports multiple listeners and callbacks
  // survive the caller's PJRT_Event_Destroy — true for the XLA reference
  // implementation (libtpu, CPU/GPU plugins) but not a stated C-API
  // guarantee, so VTPU_D2H_EVENT_HOOK=0 opts out for plugins with
  // single-listener semantics (falls back to charging the sync portion).
  bool hooked = false;
  if (s.limits.d2h_event_hook && args->event != nullptr &&
      s.real->PJRT_Event_OnReady != nullptr) {
    auto* ctx = new D2hCtx{dev_idx, t0, cap_ok, pending_total};
    PJRT_Event_OnReady_Args on;
    std::memset(&on, 0, sizeof(on));
    on.struct_size = PJRT_Event_OnReady_Args_STRUCT_SIZE;
    on.event = args->event;
    on.callback = d2h_done_cb;
    on.user_arg = ctx;
    if (PJRT_Error* oerr = s.real->PJRT_Event_OnReady(&on)) {
      delete ctx;
      PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, oerr};
      s.real->PJRT_Error_Destroy(&d);
    } else {
      hooked = true;
    }
  }
  if (!hooked) {
    g_d2h_inflight.fetch_sub(1, std::memory_order_relaxed);
    st.tohost_ns.fetch_add(t1 - t0, std::memory_order_relaxed);
    charge_sync_wall(dev_idx, t0, t1, cap_ok ? (int)pending_total : -1);
  }
  return err;
}

PJRT_Error* wrapped_buffer_destroy(PJRT_Buffer_Destroy_Args* args) {
  auto& s = S();
  size_t dev_idx = 0;
  uint64_t bytes = 0;
  {
    std::lock_guard<std::mutex> lock(s.mu);
    auto it = s.buffers.find(args->buffer);
    if (it != s.buffers.end()) {
      dev_idx = it->second.first;
      bytes = it->second.second;
#ifdef VTPU_SEEDED_UAF
      // Sanitizer-tier control build ONLY (`make asan-seeded`): read the
      // map entry after erase() frees its node — the exact use-after-free a
      // racing Buffer_Destroy would produce. The tier must flag this.
      auto* entry = &it->second;
      s.buffers.erase(it);
      bytes = entry->second;
#else
      s.buffers.erase(it);
#endif
      auto& dev = s.dev(dev_idx);
      dev.used_bytes = dev.used_bytes >= bytes ? dev.used_bytes - bytes : 0;
    }
  }
  if (bytes && s.region) s.region->add_used(dev_idx, -(int64_t)bytes);
  return s.real->PJRT_Buffer_Destroy(args);
}

PJRT_Error* wrapped_client_destroy(PJRT_Client_Destroy_Args* args) {
  // Stop the calibration oracle's re-attestation thread from touching the
  // dying client (no-op for clients other than the attested one; the last
  // verdict stays in force for the process).
  calib::on_client_destroy(args->client);
  // Memory-space, device, executable and buffer handles die with their
  // client; their addresses can be reused by the next client with different
  // semantics, so flush every cache keyed by them (the shape-size cache is
  // address-free and stays). Outstanding buffer accounting is released the
  // same way — the HBM really is freed — including the monitor's region view.
  {
    std::lock_guard<std::mutex> lock(g_memkind_mu);
    g_memkind_cache.clear();
  }
  {
    std::lock_guard<std::mutex> lock(g_execmeta_mu);
    g_execmeta.clear();
  }
  auto& s = S();
  std::vector<uint64_t> released;
  {
    std::lock_guard<std::mutex> lock(s.mu);
    s.device_index.clear();
    s.device_count.store(0, std::memory_order_relaxed);
    s.buffers.clear();
    released.resize(s.devices.size(), 0);
    for (size_t i = 0; i < s.devices.size(); i++) {
      released[i] = s.devices[i].used_bytes;
      s.devices[i].used_bytes = 0;
    }
  }
  if (s.region) {
    for (size_t i = 0; i < released.size(); i++) {
      if (released[i]) s.region->add_used(i, -(int64_t)released[i]);
    }
  }
  return s.real->PJRT_Client_Destroy(args);
}

PJRT_Error* wrapped_loaded_executable_destroy(
    PJRT_LoadedExecutable_Destroy_Args* args) {
  // Drop the cached output metadata BEFORE the real destroy: the allocator
  // can reuse this address for a new executable with a different output
  // count/sizes, and a stale hit would mis-account or walk past output_lists.
  {
    std::lock_guard<std::mutex> lock(g_execmeta_mu);
    g_execmeta.erase(args->executable);
  }
  return S().real->PJRT_LoadedExecutable_Destroy(args);
}

struct ExecDoneCtx {
  size_t dev_idx;
  uint64_t submit_ns;
  uint64_t precharge_ns;  // exactly what admit() pre-charged (0 = unenforced)
  PJRT_Event* own_event;  // non-null when the SHIM requested the event
};

void exec_done_cb(PJRT_Error* error, void* user_arg) {
  auto* ctx = static_cast<ExecDoneCtx*>(user_arg);
  auto& s = S();
  uint64_t now = tick_ns();
  uint64_t busy = now > ctx->submit_ns ? now - ctx->submit_ns : 0;
  if (busy > 0 && calib::verdict() == calib::kTransportPolluted) {
    // Attested TRANSPORT_POLLUTED events (calib.h): completion events are
    // real but their delivery rides a proxy transport, so every settle interval
    // carries ~the idle-transport baseline — the r05_13 storm failure,
    // where the event-fed cap budget itself inflated with weather. Deduct
    // the ATTESTED baseline (measured against a known-duration probe, not
    // a tenant-movable signal), bounded like the charge floor so a settle
    // always pays at least 1/16 of its observed interval.
    uint64_t base = calib::transport_baseline_ns();
    uint64_t max_exempt = busy - busy / 16;
    if (base > max_exempt) base = max_exempt;
    busy -= base;
    now = ctx->submit_ns + busy;
  }
  stats().settles.fetch_add(1, std::memory_order_relaxed);
  stats().settled_busy_ns.fetch_add(busy, std::memory_order_relaxed);
  g_settles.fetch_add(1, std::memory_order_relaxed);
  g_settled_busy_ns.fetch_add(busy, std::memory_order_relaxed);
  {
    std::lock_guard<std::mutex> lock(s.mu);
    s.dev(ctx->dev_idx).limiter->settle_interval(ctx->submit_ns, now,
                                                 ctx->precharge_ns);
  }
  if (s.region) {
    std::lock_guard<std::mutex> lock(s.mu);
    s.region->set_core_util(
        ctx->dev_idx, s.dev(ctx->dev_idx).limiter->current_util_percent(now));
  }
  if (error != nullptr) {
    PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, error};
    s.real->PJRT_Error_Destroy(&d);
  }
  destroy_event(ctx->own_event);
  delete ctx;
}

PJRT_Error* wrapped_execute(PJRT_LoadedExecutable_Execute_Args* args) {
  auto& s = S();
  auto& st = stats();
  st.executes.fetch_add(1, std::memory_order_relaxed);
  g_execs_since_d2h.fetch_add(1, std::memory_order_relaxed);
  size_t dev_idx =
      args->execute_device ? device_index_of(args->execute_device) : 0;

  // Priority gate: the monitor suspends low-priority work by writing
  // recent_kernel = -1 (reference feedback.go:104-134 semantics). Blocks
  // until unblocked; any release-without-unblock is region-controlled
  // (gate_timeout_ms / stale monitor heartbeat) and counted.
  if (s.region != nullptr) {
    ScopedNs timer(st.gate_ns);
    bool forced = false;
    s.region->gate_wait(&forced);
  }

  uint64_t waited = 0;
  bool enforce = s.limits.core_enforced() &&
                 (s.region == nullptr || s.region->utilization_enforced());
  DutyCycleLimiter* limiter;
  {
    std::lock_guard<std::mutex> lock(s.mu);
    limiter = s.dev(dev_idx).limiter;
  }
  uint64_t precharge_ns = 0;
  if (enforce) {
    ScopedNs timer(st.admit_ns);
    waited = limiter->admit(now_ns(), &precharge_ns);
  }

  // Busy-time feedback needs a completion event. JAX does NOT request
  // device_complete_events, and without one the limiter would charge its
  // initial EMA estimate forever — the core knob would be decorative on
  // every real workload. So when the caller passed nullptr and feedback
  // matters (a core limit is enforced, or a region reports utilization),
  // the shim requests its OWN events and destroys them in the callback.
  std::vector<PJRT_Event*> own_events;
  bool synthesized = false;
  bool want_feedback = enforce || s.region != nullptr;
  if (want_feedback && args->device_complete_events == nullptr &&
      args->num_devices >= 1 && s.real->PJRT_Event_OnReady != nullptr &&
      s.real->PJRT_Event_Destroy != nullptr) {
    own_events.assign(args->num_devices, nullptr);
    args->device_complete_events = own_events.data();
    synthesized = true;
  }

  uint64_t submit_ns = tick_ns();  // monotonic: interval math in the limiter
  PJRT_Error* err;
  {
    ScopedNs timer(st.enqueue_ns);
    err = s.real->PJRT_LoadedExecutable_Execute(args);
  }
  if (s.region) {
    ScopedNs timer(st.region_ns);
    s.region->record_kernel(dev_idx, waited);
  }
  if (synthesized) {
    // the caller never asked for events; restore its view of the struct
    args->device_complete_events = nullptr;
  }
  if (err != nullptr) return err;  // on error the events are not populated

  // Ride the first row's completion event (caller-provided or our own).
  bool hooked = false;
  PJRT_Event* ev = synthesized
                       ? own_events[0]
                       : (args->device_complete_events != nullptr &&
                                  args->num_devices >= 1
                              ? args->device_complete_events[0]
                              : nullptr);
  if (ev != nullptr && s.real->PJRT_Event_OnReady != nullptr) {
    ScopedNs timer(st.onready_ns);
    auto* ctx = new ExecDoneCtx{dev_idx, submit_ns, precharge_ns,
                                synthesized ? ev : nullptr};
    PJRT_Event_OnReady_Args on;
    std::memset(&on, 0, sizeof(on));
    on.struct_size = PJRT_Event_OnReady_Args_STRUCT_SIZE;
    on.event = ev;
    on.callback = exec_done_cb;
    on.user_arg = ctx;
    PJRT_Error* oerr = s.real->PJRT_Event_OnReady(&on);
    if (oerr == nullptr) {
      hooked = true;
    } else {
      delete ctx;
      PJRT_Error_Destroy_Args d{PJRT_Error_Destroy_Args_STRUCT_SIZE, nullptr, oerr};
      s.real->PJRT_Error_Destroy(&d);
    }
  }
  // Synthesized events for rows past 0 (or an unhookable row 0) are ours to
  // destroy; do it now, their timing isn't read.
  if (synthesized) {
    for (size_t d = hooked ? 1 : 0; d < own_events.size(); d++) {
      destroy_event(own_events[d]);
    }
  }
  if (!hooked) {
    // No completion signal: the pre-charged estimate stands as the cost.
    limiter->settle(limiter->estimate_ns(), submit_ns, precharge_ns);
  }

  // Account execute outputs so the cap covers results, not just host uploads.
  // Steady state costs ZERO PJRT round-trips: output shapes are static per
  // executable, so sizes observed on the first execute are replayed from
  // ExecMeta, and the whole row lands as one batched region write. (The cold
  // query on a fresh output can block until the buffer is defined — over a
  // proxied plugin that serializes the async dispatch, which was the bulk of
  // the r2 +19.5% TTFT overhead.)
  if (args->output_lists != nullptr) {
    ScopedNs timer(st.acct_ns);
    size_t num_outputs = executable_num_outputs(args->executable);
    std::vector<uint64_t> sizes = cached_output_sizes(args->executable);
    bool have_cache = sizes.size() == num_outputs && num_outputs > 0;
    if (have_cache) {
      st.size_cache_hits.fetch_add(1, std::memory_order_relaxed);
    } else if (num_outputs > 0) {
      st.size_cache_misses.fetch_add(1, std::memory_order_relaxed);
    }
    bool stored = false;
    for (size_t d = 0; d < args->num_devices; d++) {
      PJRT_Buffer** outs = args->output_lists[d];
      if (outs == nullptr) continue;
      // Multi-device launches (execute_device == null) place row d's outputs
      // on addressable device d; a pinned launch puts them on dev_idx.
      size_t out_dev = args->execute_device ? dev_idx : d;
      if (!have_cache) {
        // Cold path: query each output once; SPMD rows share shard shapes,
        // so row 0's sizes are cached for every later execute. A row with a
        // null (elided) output is NOT cached — a 0 stored for that index
        // would be replayed forever even when later executes populate it.
        sizes.assign(num_outputs, 0);
        bool complete = num_outputs > 0;
        for (size_t o = 0; o < num_outputs; o++) {
          if (outs[o] != nullptr) {
            sizes[o] = buffer_device_size(outs[o]);
          } else {
            complete = false;
          }
        }
        if (!stored && complete && !size_cache_disabled()) {
          store_output_sizes(args->executable, sizes);
          stored = true;
        }
      }
      account_output_row(outs, sizes.data(), num_outputs, out_dev);
    }
  }
  return nullptr;
}

// ---------------------------------------------------------------- api table

template <typename F>
void replace_field(F** slot, const PJRT_Api* real, F* replacement) {
  // Only wrap fields that exist within the runtime struct_size.
  auto offset = reinterpret_cast<const char*>(slot) -
                reinterpret_cast<const char*>(&S().wrapped);
  if (offset + (ptrdiff_t)sizeof(void*) <= (ptrdiff_t)real->struct_size) {
    *slot = replacement;
  }
}

const PJRT_Api* wrap_api(const PJRT_Api* real) {
  auto& s = S();
  if (s.real == real) return &s.wrapped;
  s.real = real;
  std::memset(&s.wrapped, 0, sizeof(s.wrapped));
  std::memcpy(&s.wrapped, real,
              real->struct_size < sizeof(s.wrapped) ? real->struct_size
                                                    : sizeof(s.wrapped));
  s.wrapped.struct_size = real->struct_size < sizeof(s.wrapped)
                              ? real->struct_size
                              : sizeof(s.wrapped);
  replace_field(&s.wrapped.PJRT_Error_Destroy, real, wrapped_error_destroy);
  replace_field(&s.wrapped.PJRT_Error_Message, real, wrapped_error_message);
  replace_field(&s.wrapped.PJRT_Error_GetCode, real, wrapped_error_getcode);
  replace_field(&s.wrapped.PJRT_Client_Create, real, wrapped_client_create);
  replace_field(&s.wrapped.PJRT_Client_Destroy, real, wrapped_client_destroy);
  replace_field(&s.wrapped.PJRT_Client_BufferFromHostBuffer, real,
                wrapped_buffer_from_host);
  // Read presence from s.wrapped (memcpy'd to struct_size, zeroed beyond),
  // never from real fields that may lie past an older plugin's struct.
  if (s.wrapped.PJRT_Client_CreateUninitializedBuffer != nullptr) {
    replace_field(&s.wrapped.PJRT_Client_CreateUninitializedBuffer, real,
                  wrapped_create_uninitialized);
  }
  if (s.wrapped.PJRT_Buffer_CopyToDevice != nullptr) {
    replace_field(&s.wrapped.PJRT_Buffer_CopyToDevice, real, wrapped_copy_to_device);
  }
  if (s.wrapped.PJRT_Buffer_CopyToMemory != nullptr) {
    replace_field(&s.wrapped.PJRT_Buffer_CopyToMemory, real, wrapped_copy_to_memory);
  }
  replace_field(&s.wrapped.PJRT_Buffer_Destroy, real, wrapped_buffer_destroy);
  if (s.wrapped.PJRT_Event_Await != nullptr) {
    replace_field(&s.wrapped.PJRT_Event_Await, real, wrapped_event_await);
  }
  if (s.wrapped.PJRT_Buffer_ToHostBuffer != nullptr) {
    replace_field(&s.wrapped.PJRT_Buffer_ToHostBuffer, real, wrapped_to_host);
  }
  replace_field(&s.wrapped.PJRT_LoadedExecutable_Execute, real, wrapped_execute);
  replace_field(&s.wrapped.PJRT_LoadedExecutable_Destroy, real,
                wrapped_loaded_executable_destroy);
  VTPU_INFO("wrapped PJRT api (struct_size=%zu, version %d.%d)",
            real->struct_size, real->pjrt_api_version.major_version,
            real->pjrt_api_version.minor_version);
  return &s.wrapped;
}

}  // namespace
}  // namespace vtpu

// ------------------------------------------------------------------ exports

extern "C" {

typedef const PJRT_Api* (*GetPjrtApiFn)();

// Delivery B: libvtpu.so IS the PJRT plugin; real one comes from
// VTPU_REAL_LIBTPU (default /lib/libtpu.so, the TPU VM location).
const PJRT_Api* GetPjrtApi() {
  static const PJRT_Api* api = []() -> const PJRT_Api* {
    const char* path = std::getenv("VTPU_REAL_LIBTPU");
    if (path == nullptr) path = "/lib/libtpu.so";
    void* handle = dlopen(path, RTLD_NOW | RTLD_LOCAL);
    if (handle == nullptr) {
      VTPU_FATAL_HEALTH("dlopen real PJRT plugin failed",
                        "cannot dlopen real plugin %s: %s", path, dlerror());
      return nullptr;
    }
    auto fn = (GetPjrtApiFn)dlsym(handle, "GetPjrtApi");
    if (fn == nullptr) {
      VTPU_FATAL_HEALTH("real PJRT plugin exports no GetPjrtApi",
                        "no GetPjrtApi in %s", path);
      return nullptr;
    }
    return vtpu::wrap_api(fn());
  }();
  return api;
}

// Test/introspection hooks (also used by the Python ctypes tests).
uint64_t vtpu_device_used_bytes(size_t idx) {
  auto& s = vtpu::S();
  std::lock_guard<std::mutex> lock(s.mu);
  return idx < s.devices.size() ? s.devices[idx].used_bytes : 0;
}
uint64_t vtpu_device_limit_bytes(size_t idx) {
  return vtpu::S().limits.limit_for(idx);
}
const PJRT_Api* vtpu_wrap_api_for_test(const PJRT_Api* real) {
  return vtpu::wrap_api(real);
}

// Hot-path cost attribution (BASELINE.md "libvtpu overhead"): cumulative
// per-wrapper nanoseconds + PJRT round-trip counts since start (or last
// reset), as one JSON object. Returns bytes written (excluding NUL).
size_t vtpu_stats_json(char* buf, size_t cap) {
  auto& st = vtpu::stats();
  vtpu::calib::Snapshot cal = vtpu::calib::snapshot();
  int n = std::snprintf(
      buf, cap,
      "{\"executes\": %llu, \"gate_ns\": %llu, \"admit_ns\": %llu, "
      "\"enqueue_ns\": %llu, \"onready_ns\": %llu, \"acct_ns\": %llu, "
      "\"size_rpcs\": %llu, \"size_rpc_ns\": %llu, \"numout_rpc_ns\": %llu, "
      "\"memkind_rpcs\": %llu, \"memkind_rpc_ns\": %llu, "
      "\"uploads\": %llu, \"upload_ns\": %llu, \"upload_real_ns\": %llu, "
      "\"region_ns\": %llu, \"size_cache_hits\": %llu, "
      "\"size_cache_misses\": %llu, \"settles\": %llu, "
      "\"settled_busy_ns\": %llu, \"tohost_calls\": %llu, "
      "\"tohost_ns\": %llu, \"await_calls\": %llu, "
      "\"await_ns\": %llu, \"d2h_capped\": %llu, "
      "\"d2h_floored\": %llu, \"d2h_uncapped\": %llu, "
      "\"d2h_attested\": %llu, "
      "\"d2h_gate_inflight\": %llu, \"d2h_gate_size\": %llu, "
      "\"d2h_gate_multichip\": %llu, \"d2h_errors\": %llu, "
      "\"sync_charged_ns\": %llu, \"rtt_floor_ns\": %llu, "
      "\"calib_verdict\": %d, \"calib_fallback\": %u, "
      "\"calib_ratio_ppm\": %llu, \"calib_baseline_ns\": %llu, "
      "\"calib_probe_ns\": %llu, \"calib_recalibs\": %llu, "
      "\"calib_busy_ns\": %llu}",
      (unsigned long long)st.executes.load(),
      (unsigned long long)st.gate_ns.load(),
      (unsigned long long)st.admit_ns.load(),
      (unsigned long long)st.enqueue_ns.load(),
      (unsigned long long)st.onready_ns.load(),
      (unsigned long long)st.acct_ns.load(),
      (unsigned long long)st.size_rpcs.load(),
      (unsigned long long)st.size_rpc_ns.load(),
      (unsigned long long)st.numout_rpc_ns.load(),
      (unsigned long long)st.memkind_rpcs.load(),
      (unsigned long long)st.memkind_rpc_ns.load(),
      (unsigned long long)st.uploads.load(),
      (unsigned long long)st.upload_ns.load(),
      (unsigned long long)st.upload_real_ns.load(),
      (unsigned long long)st.region_ns.load(),
      (unsigned long long)st.size_cache_hits.load(),
      (unsigned long long)st.size_cache_misses.load(),
      (unsigned long long)st.settles.load(),
      (unsigned long long)st.settled_busy_ns.load(),
      (unsigned long long)st.tohost_calls.load(),
      (unsigned long long)st.tohost_ns.load(),
      (unsigned long long)st.await_calls.load(),
      (unsigned long long)st.await_ns.load(),
      (unsigned long long)st.d2h_capped.load(),
      (unsigned long long)st.d2h_floored.load(),
      (unsigned long long)st.d2h_uncapped.load(),
      (unsigned long long)st.d2h_attested.load(),
      (unsigned long long)st.d2h_gate_inflight.load(),
      (unsigned long long)st.d2h_gate_size.load(),
      (unsigned long long)st.d2h_gate_multichip.load(),
      (unsigned long long)st.d2h_errors.load(),
      (unsigned long long)st.sync_charged_ns.load(),
      (unsigned long long)vtpu::base_charge_floor_ns(vtpu::S().limits),
      (int)cal.verdict, (unsigned)cal.fallback_engaged,
      (unsigned long long)cal.ratio_ppm,
      (unsigned long long)cal.baseline_ns,
      (unsigned long long)cal.probe_ns,
      (unsigned long long)cal.recalibs,
      (unsigned long long)cal.probe_busy_ns);
  return n > 0 && (size_t)n < cap ? (size_t)n : 0;
}

void vtpu_stats_reset() {
  auto& st = vtpu::stats();
  st.executes = 0;
  st.gate_ns = 0;
  st.admit_ns = 0;
  st.enqueue_ns = 0;
  st.onready_ns = 0;
  st.acct_ns = 0;
  st.size_rpcs = 0;
  st.size_rpc_ns = 0;
  st.numout_rpc_ns = 0;
  st.memkind_rpcs = 0;
  st.memkind_rpc_ns = 0;
  st.uploads = 0;
  st.upload_ns = 0;
  st.upload_real_ns = 0;
  st.region_ns = 0;
  st.size_cache_hits = 0;
  st.size_cache_misses = 0;
  st.settles = 0;
  st.settled_busy_ns = 0;
  st.tohost_calls = 0;
  st.tohost_ns = 0;
  st.await_calls = 0;
  st.await_ns = 0;
  st.d2h_capped = 0;
  st.d2h_floored = 0;
  st.d2h_uncapped = 0;
  st.d2h_attested = 0;
  st.d2h_gate_inflight = 0;
  st.d2h_gate_size = 0;
  st.d2h_gate_multichip = 0;
  st.d2h_errors = 0;
  st.sync_charged_ns = 0;
}

// Delivery A: dlsym interposition. Any GetPjrtApi resolution in the process
// returns a trampoline that wraps the real table.
static const PJRT_Api* trampoline_get_pjrt_api();
static GetPjrtApiFn g_real_get_pjrt_api = nullptr;

static const PJRT_Api* trampoline_get_pjrt_api() {
  if (g_real_get_pjrt_api == nullptr) return nullptr;
  return vtpu::wrap_api(g_real_get_pjrt_api());
}

typedef void* (*DlsymFn)(void*, const char*);

// The interposed dlsym (and everything it calls before the real symbol is
// resolved) can run EARLIER than any runtime in the process is ready for:
// sanitizer runtimes in particular call dlsym during their own init, before
// shadow memory exists, and bind to THIS definition. So the whole path is
// (a) uninstrumented (no_sanitize) and (b) libc-interceptor-free — no
// strcmp, no C++ static-guard lambda, only dlvsym + __atomic builtins.
__attribute__((no_sanitize("address", "undefined")))
static DlsymFn real_dlsym_resolver() {
  static DlsymFn real = nullptr;  // idempotent resolution; relaxed atomics
  DlsymFn cached = __atomic_load_n(&real, __ATOMIC_RELAXED);
  if (cached != nullptr) return cached;
  // dlvsym is itself safe to call; glibc symbol versions vary by arch.
  static const char* const kVers[] = {"GLIBC_2.2.5", "GLIBC_2.17",
                                      "GLIBC_2.27",  "GLIBC_2.34",
                                      "GLIBC_2.4",   "GLIBC_2.0"};
  for (const char* ver : kVers) {
    if (void* p = dlvsym(RTLD_NEXT, "dlsym", ver)) {
      __atomic_store_n(&real, (DlsymFn)p, __ATOMIC_RELAXED);
      return (DlsymFn)p;
    }
  }
  // Silently breaking every dlsym in the process would be far worse than
  // crashing loudly: bail with an actionable message (use the plugin-
  // shadowing delivery instead of LD_PRELOAD on this libc).
  std::fprintf(stderr,
               "[libvtpu] FATAL: cannot resolve the real dlsym on this libc; "
               "remove libvtpu from LD_PRELOAD and use TPU_LIBRARY_PATH="
               "libvtpu.so with VTPU_REAL_LIBTPU instead\n");
  std::abort();
}

__attribute__((no_sanitize("address", "undefined")))
static bool is_get_pjrt_api(const char* name) {
  // manual compare: libc strcmp may be sanitizer-intercepted and this can
  // run before that runtime is initialized
  static const char kTarget[] = "GetPjrtApi";
  if (name == nullptr) return false;
  size_t i = 0;
  while (kTarget[i] != '\0' && name[i] == kTarget[i]) i++;
  return kTarget[i] == '\0' && name[i] == '\0';
}

__attribute__((no_sanitize("address", "undefined")))
void* dlsym(void* handle, const char* name) {
  DlsymFn real = real_dlsym_resolver();
  void* sym = real(handle, name);
  if (is_get_pjrt_api(name) && sym != nullptr) {
    // Do not re-wrap our own export (delivery B handles itself).
    if (sym == (void*)&GetPjrtApi) return sym;
    g_real_get_pjrt_api = (GetPjrtApiFn)sym;
    VTPU_INFO("intercepted GetPjrtApi resolution");
    return (void*)&trampoline_get_pjrt_api;
  }
  return sym;
}

}  // extern "C"
