// TensorCore duty-cycle limiter: queue-level pacing of PJRT executions.
//
// TPUs have no SM-mask analog — the enforceable knob is WHEN work is enqueued.
// Implemented as a busy-time token bucket: allowance accrues at limit% of wall
// time (burst-capped at one window's budget); every execution pre-charges an
// estimated busy time at submit and settles the difference when its completion
// event fires (caller-requested events), or keeps the EMA estimate otherwise.
// admit() sleeps until the allowance covers the next execution, which pins the
// long-run duty cycle at the limit.
// This is the TPU-first re-design of the reference's SM throttle
// (HAMi-core CUDA_DEVICE_SM_LIMIT; SURVEY §2.4 "queue-level pacing").
#ifndef VTPU_LIMITER_H_
#define VTPU_LIMITER_H_

#include <atomic>
#include <cstdint>
#include <mutex>

namespace vtpu {

class DutyCycleLimiter {
 public:
  explicit DutyCycleLimiter(int limit_percent, uint64_t window_ns = 100'000'000ull)
      : limit_percent_(limit_percent), window_ns_(window_ns) {}

  // Block until the allowance covers the next execution, then pre-charge the
  // capped requirement (never more than one window's burst budget — settle
  // reconciles the observed cost either way, and pre-charging a transport-
  // anomaly-inflated EMA would sink tokens windows-negative and stall later
  // admits until the refund lands). Returns the nanoseconds waited; the
  // amount actually pre-charged is written to *precharge_ns (0 when not
  // enforcing) and must be passed back to the matching settle call.
  uint64_t admit(uint64_t now_ns, uint64_t* precharge_ns = nullptr);

  // Settle a completed execution: refund exactly what admit() pre-charged
  // (precharge_ns, 0 for unenforced submissions — then no token debt) and
  // charge the observed busy time; always update the EMA and util window.
  void settle(uint64_t busy_ns, uint64_t now_ns, uint64_t precharge_ns);

  // Settle a completed execution from its MONOTONIC [submit, ready] interval,
  // with UNION accounting against every other charged interval: time already
  // charged (e.g. by charge_interval from a blocking D2H) is never charged
  // twice. The EMA estimate tracks the union-charged (device-attributed)
  // cost — NOT the raw submit->ready latency, which on a deep pipeline
  // includes the whole queue wait and would ratchet past the admit budget.
  void settle_interval(uint64_t start_ns, uint64_t end_ns, uint64_t precharge_ns);

  // Charge device busy that is NOT tenant work (the calibration oracle's own
  // probes, src/calib.*): it lands in the util window — the monitor's view
  // stays truthful about what occupied the chip — but never debits the token
  // bucket, never feeds the per-execute EMA, and never enters the union set,
  // so a bounded re-attestation cadence can never pace the tenant or distort
  // its estimates.
  void charge_busy_unpaced(uint64_t busy_ns, uint64_t now_ns);

  // Charge a wall-clock interval the process spent blocked ON the runtime
  // (D2H reads, event waits). This is the busy signal of last resort:
  // proxied runtimes fulfill completion events at ENQUEUE (observed:
  // 70 settlements totalling 22 ms for ~8 s of real compute), so submission-
  // side intervals are the only truthful clock there. Union accounting makes
  // it a no-op wherever faithful completion events already charged the time.
  void charge_interval(uint64_t start_ns, uint64_t end_ns);

  bool enforcing() const { return limit_percent_ > 0 && limit_percent_ < 100; }

  int current_util_percent(uint64_t now_ns);

  uint64_t estimate_ns() const {
    // stats reads race the locked writers by design; atomic keeps the
    // unlocked read defined (torn 64-bit reads are UB, not just stale)
    return est_ns_.load(std::memory_order_relaxed);
  }

 private:
  void refill(uint64_t now_ns);
  void accum_busy(uint64_t busy_ns, uint64_t now_ns);

  // Union accounting over RECENT charged intervals (sorted, disjoint,
  // merged): charges report only their uncovered portion. A set rather than
  // a single high-water mark because completion callbacks arrive on
  // detached threads with no end-time ordering guarantee — a late-delivered
  // early interval must still pay for its uncovered time. Entries older
  // than the coverage horizon are pruned.
  struct ChargedIv {
    uint64_t s, e;
  };
  static constexpr int kMaxIvs = 8;
  ChargedIv ivs_[kMaxIvs];
  int n_ivs_ = 0;
  // Returns the uncovered length of [s, e) and inserts it into the set
  // (caller holds mu_).
  uint64_t uncovered_and_insert(uint64_t s, uint64_t e);

  int limit_percent_;
  uint64_t window_ns_;
  std::mutex mu_;
  int64_t tokens_ns_ = 0;     // accrued busy allowance (may go negative)
  uint64_t last_refill_ns_ = 0;
  // 1ms initial per-execute estimate; atomic for the lock-free stats read
  // (writers all hold mu_, so relaxed ordering suffices)
  std::atomic<uint64_t> est_ns_{1'000'000ull};
  // recent-busy tracking for util reporting
  uint64_t busy_accum_ns_ = 0;
  uint64_t busy_epoch_ns_ = 0;
};

}  // namespace vtpu

#endif  // VTPU_LIMITER_H_
