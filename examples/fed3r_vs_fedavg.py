"""FED3R vs gradient FL under pathological heterogeneity (paper Fig. 2).

Compares accuracy-vs-rounds and the App. D/E cost meters for FED3R,
FED3R-RF and the FedAvg/FedAvgM/Scaffold linear-probe baselines on the
same one-class-per-client federation.

    PYTHONPATH=src python examples/fed3r_vs_fedavg.py
"""
from dataclasses import replace

from repro.configs.base import Fed3RConfig, FederatedConfig
from repro.data import make_federated_features
from repro.federated import run_fed3r
from repro.federated.costs import CostModel
from repro.federated.simulator import linear_head_task, run_federated

D, C, K = 48, 20, 100
fed, test = make_federated_features(
    seed=0, n=12_000, d=D, n_classes=C, n_clients=K, alpha=0.0, noise=2.5
)
cm = CostModel(b=2.22e6, d=D, C=C, E=1)
avg_nk = fed.client_sizes().mean()

print(f"{'method':14s} {'rounds':>7s} {'final acc':>9s} {'upload/client':>14s} "
      f"{'GFLOPs/client':>14s}")

# --- FED3R family ------------------------------------------------------------
for name, rf in (("fed3r", 0), ("fed3r-rf", 1024)):
    f3 = Fed3RConfig(n_classes=C, n_random_features=rf, rff_sigma=12.0)
    fc = FederatedConfig(n_clients=K, clients_per_round=10, n_rounds=100)
    _, _, h = run_fed3r(fed, test.features, test.labels, f3, fc, eval_every=1)
    row_cm = replace(cm, D=rf)  # the RF rows upload D-wide statistics
    up = row_cm.comm_per_client(name)["up"] * 4
    fl = row_cm.comp_per_client(name, avg_nk)
    print(f"{name:14s} {h.rounds[-1]:7d} {h.accuracy[-1]:9.4f} "
          f"{up/1e6:11.1f}MB {fl/1e9:13.2f}")

# --- gradient LP baselines -----------------------------------------------------
for alg, smom in (("fedavg", 0.0), ("fedavgm", 0.9), ("scaffold", 0.0)):
    task = linear_head_task(D, C, test.features, test.labels)
    fc = FederatedConfig(
        n_clients=K, clients_per_round=10, n_rounds=100, local_epochs=1,
        local_batch_size=32, client_lr=0.1, algorithm=alg,
        server_momentum=smom,
    )
    _, h = run_federated(task, fed, fc, eval_every=10)
    lp = ("fedavg" if alg != "scaffold" else "scaffold") + "-lp"
    up = cm.comm_per_client(lp)["up"] * 4 * 100  # pays every round
    fl = cm.cumulative_comp_flops_per_client(lp, 100, 10, K, avg_nk)[-1]
    print(f"{alg+'-lp':14s} {100:7d} {h.accuracy[-1]:9.4f} "
          f"{up/1e6:11.1f}MB {fl/1e9:13.2f}")
