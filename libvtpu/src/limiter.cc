#include "limiter.h"

#include <time.h>

#include <algorithm>

namespace vtpu {

static void sleep_ns(uint64_t ns) {
  struct timespec ts;
  ts.tv_sec = ns / 1000000000ull;
  ts.tv_nsec = ns % 1000000000ull;
  nanosleep(&ts, nullptr);
}

static uint64_t mono_now_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return uint64_t(ts.tv_sec) * 1000000000ull + ts.tv_nsec;
}

void DutyCycleLimiter::refill(uint64_t now_ns) {
  if (last_refill_ns_ == 0) {
    last_refill_ns_ = now_ns;
    tokens_ns_ = (int64_t)(window_ns_ * limit_percent_ / 100);  // initial burst
    return;
  }
  if (now_ns <= last_refill_ns_) return;
  uint64_t elapsed = now_ns - last_refill_ns_;
  last_refill_ns_ = now_ns;
  int64_t burst_cap = (int64_t)(window_ns_ * limit_percent_ / 100);
  tokens_ns_ += (int64_t)(elapsed * limit_percent_ / 100);
  tokens_ns_ = std::min(tokens_ns_, burst_cap);
}

uint64_t DutyCycleLimiter::admit(uint64_t now_ns, uint64_t* precharge_ns) {
  if (precharge_ns) *precharge_ns = 0;
  if (limit_percent_ <= 0 || limit_percent_ >= 100) return 0;
  uint64_t waited = 0;
  std::unique_lock<std::mutex> lock(mu_);
  for (;;) {
    refill(mono_now_ns());
    // The requirement must stay satisfiable: tokens are burst-capped at one
    // window's budget, so an estimate above the cap (e.g. queue latency on
    // a deep pipeline leaking into the EMA) would otherwise spin forever.
    int64_t burst_cap = (int64_t)(window_ns_ * limit_percent_ / 100);
    int64_t est = (int64_t)est_ns_.load(std::memory_order_relaxed);
    int64_t need = est < burst_cap ? est : burst_cap;
    // Floor at 1 ns: a zero pre-charge reads as "unenforced" to settle(),
    // which would let an enforced execution whose EMA decayed to 0 skip its
    // busy-time debit entirely.
    if (need < 1) need = 1;
    if (tokens_ns_ >= need) {
      // Pre-charge only the capped requirement, not the raw EMA: after a
      // clamped transport-anomaly charge inflates the estimate, the full
      // est_ns_ could sink tokens many windows negative and stall every
      // subsequent admit until its settle refund lands. settle() refunds
      // this exact amount and charges the observed cost instead.
      tokens_ns_ -= need;
      if (precharge_ns) *precharge_ns = (uint64_t)need;
      return waited;
    }
    uint64_t deficit = (uint64_t)(need - tokens_ns_);
    uint64_t delay = std::max<uint64_t>(
        deficit * 100 / std::max(1, limit_percent_), 200'000ull);
    delay = std::min(delay, window_ns_);
    lock.unlock();
    sleep_ns(delay);
    lock.lock();
    waited += delay;
  }
}

void DutyCycleLimiter::settle(uint64_t busy_ns, uint64_t now_ns,
                              uint64_t precharge_ns) {
  std::lock_guard<std::mutex> lock(mu_);
  if (precharge_ns > 0 && limit_percent_ > 0 && limit_percent_ < 100) {
    refill(mono_now_ns());
    // Replace exactly what admit() pre-charged with the observed cost.
    tokens_ns_ += (int64_t)precharge_ns;
    tokens_ns_ -= (int64_t)busy_ns;
  }
  est_ns_.store((est_ns_.load(std::memory_order_relaxed) * 7 + busy_ns) / 8,
                std::memory_order_relaxed);  // EMA, 1/8 weight
  accum_busy(busy_ns, now_ns);
}

void DutyCycleLimiter::accum_busy(uint64_t busy_ns, uint64_t now_ns) {
  // util reporting window (caller holds mu_)
  if (busy_epoch_ns_ == 0 || now_ns - busy_epoch_ns_ > 10 * window_ns_) {
    busy_epoch_ns_ = now_ns;
    busy_accum_ns_ = 0;
  }
  busy_accum_ns_ += busy_ns;
}

uint64_t DutyCycleLimiter::uncovered_and_insert(uint64_t s, uint64_t e) {
  if (e <= s) return 0;
  // subtract existing coverage
  uint64_t covered = 0;
  for (int i = 0; i < n_ivs_; i++) {
    uint64_t os = ivs_[i].s > s ? ivs_[i].s : s;
    uint64_t oe = ivs_[i].e < e ? ivs_[i].e : e;
    if (oe > os) covered += oe - os;
  }
  uint64_t len = e - s;
  uint64_t uncovered = covered < len ? len - covered : 0;
  // insert + merge with any overlapping/adjacent entries
  for (int i = 0; i < n_ivs_;) {
    if (ivs_[i].e >= s && ivs_[i].s <= e) {
      if (ivs_[i].s < s) s = ivs_[i].s;
      if (ivs_[i].e > e) e = ivs_[i].e;
      ivs_[i] = ivs_[--n_ivs_];
    } else {
      i++;
    }
  }
  // prune beyond the coverage horizon (late arrivals older than this are
  // charged in full — conservative in the limit's favor), and make room
  uint64_t horizon = e > 10 * window_ns_ ? e - 10 * window_ns_ : 0;
  for (int i = 0; i < n_ivs_;) {
    if (ivs_[i].e < horizon) {
      ivs_[i] = ivs_[--n_ivs_];
    } else {
      i++;
    }
  }
  if (n_ivs_ == kMaxIvs) {  // evict the oldest to keep the set bounded
    int oldest = 0;
    for (int i = 1; i < n_ivs_; i++) {
      if (ivs_[i].e < ivs_[oldest].e) oldest = i;
    }
    ivs_[oldest] = ivs_[--n_ivs_];
  }
  ivs_[n_ivs_++] = {s, e};
  return uncovered;
}

// A single CLIENT-OBSERVED wall interval far beyond the pacing window is a
// transport anomaly (a wedged proxy transport was observed billing one D2H 60 s —
// which at a 20% limit would owe FIVE MINUTES of pacing), not chip busy:
// clamp those charges to the same 10-window horizon the util view uses.
// Applied ONLY to the sync-wall path (charge_interval) — completion-event
// settles are device truth on faithful runtimes and clamping them would
// hand any tenant a quota bypass via one big fused dispatch.
static uint64_t clamp_charge(uint64_t charged, uint64_t window_ns) {
  uint64_t cap = 10 * window_ns;
  return charged < cap ? charged : cap;
}

void DutyCycleLimiter::settle_interval(uint64_t start_ns, uint64_t end_ns,
                                       uint64_t precharge_ns) {
  std::lock_guard<std::mutex> lock(mu_);
  uint64_t charged = uncovered_and_insert(start_ns, end_ns);
  if (precharge_ns > 0 && limit_percent_ > 0 && limit_percent_ < 100) {
    refill(mono_now_ns());
    tokens_ns_ += (int64_t)precharge_ns;  // refund exactly the pre-charge
    tokens_ns_ -= (int64_t)charged;
  }
  // The EMA tracks the union-charged (device-attributed) cost, NOT the raw
  // submit->ready latency: on a deep pipeline raw includes the whole queue
  // wait and would ratchet the estimate far past the admit burst budget.
  est_ns_.store((est_ns_.load(std::memory_order_relaxed) * 7 + charged) / 8,
                std::memory_order_relaxed);
  accum_busy(charged, end_ns);
}

void DutyCycleLimiter::charge_busy_unpaced(uint64_t busy_ns, uint64_t now_ns) {
  std::lock_guard<std::mutex> lock(mu_);
  accum_busy(busy_ns, now_ns);
}

void DutyCycleLimiter::charge_interval(uint64_t start_ns, uint64_t end_ns) {
  std::lock_guard<std::mutex> lock(mu_);
  uint64_t charged =
      clamp_charge(uncovered_and_insert(start_ns, end_ns), window_ns_);
  if (charged == 0) return;
  if (limit_percent_ > 0 && limit_percent_ < 100) {
    refill(mono_now_ns());
    tokens_ns_ -= (int64_t)charged;
  }
  accum_busy(charged, end_ns);
}

int DutyCycleLimiter::current_util_percent(uint64_t now_ns) {
  std::lock_guard<std::mutex> lock(mu_);
  if (busy_epoch_ns_ == 0 || now_ns <= busy_epoch_ns_) return 0;
  uint64_t span = now_ns - busy_epoch_ns_;
  uint64_t pct = busy_accum_ns_ * 100 / std::max<uint64_t>(span, 1);
  return (int)std::min<uint64_t>(pct, 100);
}

}  // namespace vtpu
