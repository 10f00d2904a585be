// Convergence-quality gate for the Bayesian optimizer: on known smooth objectives over the unit box, the GP/EI search at
// the PRODUCTION trial budget (20 observations, the
// HOROVOD_AUTOTUNE_BAYES_TRIALS default) must land within a fixed
// fraction of the dense-grid maximum.  The optimizer is deterministic
// (fixed xorshift seed), so the asserted fractions are stable.
//
// Reference counterpart: horovod's optim/bayesian_optimization.cc has no
// oracle test either — this binary is the stronger gate its 425-LoC
// implementation never had.
//
// Build + run: make -C horovod_tpu/native/cc unittest
#include <cmath>
#include <cstdio>
#include <vector>

#include "autotune.h"

namespace {

double Peak(const std::vector<double>& x, const std::vector<double>& c,
            double width) {
  double d2 = 0;
  for (size_t i = 0; i < x.size(); ++i)
    d2 += (x[i] - c[i]) * (x[i] - c[i]);
  return std::exp(-d2 / width);
}

// Smooth 2-peak objective: a broad global peak and a narrow decoy.
double Objective(const std::vector<double>& x) {
  static const std::vector<double> kMain = {0.7, 0.2, 0.5, 0.35, 0.8};
  static const std::vector<double> kDecoy = {0.15, 0.85, 0.1, 0.9, 0.2};
  std::vector<double> main_c(kMain.begin(), kMain.begin() + x.size());
  std::vector<double> decoy_c(kDecoy.begin(), kDecoy.begin() + x.size());
  return Peak(x, main_c, 0.15) + 0.45 * Peak(x, decoy_c, 0.03);
}

double GridMax(int dims, int steps) {
  std::vector<int> idx(dims, 0);
  double best = -1e300;
  while (true) {
    std::vector<double> x(dims);
    for (int d = 0; d < dims; ++d)
      x[d] = static_cast<double>(idx[d]) / (steps - 1);
    best = std::max(best, Objective(x));
    int d = 0;
    while (d < dims && ++idx[d] == steps) idx[d++] = 0;
    if (d == dims) break;
  }
  return best;
}

// One BO run at the production budget; returns best observed value.
double RunBo(int dims, int trials) {
  hvd::BayesianOptimizer bo(dims);
  for (int t = 0; t < trials; ++t) {
    std::vector<double> x = bo.NextSample();
    bo.Observe(x, Objective(x));
  }
  return bo.best_score();
}

bool Check(const char* name, double got, double want_frac, double oracle) {
  const double frac = got / oracle;
  std::printf("%-28s best=%.4f grid=%.4f frac=%.3f (need >= %.2f)  %s\n",
              name, got, oracle, frac, want_frac,
              frac >= want_frac ? "OK" : "FAIL");
  return frac >= want_frac;
}

}  // namespace

int main() {
  bool ok = true;
  // 3-D: the pre-r5 production space (cycle, fusion, cache).  21^3 grid.
  ok &= Check("bo_3d_20_trials", RunBo(3, 20), 0.95, GridMax(3, 21));
  // 5-D: the r5 space with the hierarchical booleans.  13^5 grid.
  ok &= Check("bo_5d_20_trials", RunBo(5, 20), 0.90, GridMax(5, 13));
  // Budget sanity: more trials must not do worse in 3-D.
  ok &= Check("bo_3d_40_trials", RunBo(3, 40), 0.97, GridMax(3, 21));
  if (!ok) {
    std::printf("BAYES ORACLE GATE FAILED\n");
    return 1;
  }
  std::printf("BAYES ORACLE GATE OK\n");
  return 0;
}
