"""Quickstart: FLrce on a synthetic non-iid federation, 5 minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py

Demonstrates the paper's full loop (Alg. 4): relationship-based selection,
heuristic updates, and early stopping, with resource accounting.
"""
import jax

from repro.data import make_federated_classification
from repro.fl import FLrce, run_federated
from repro.launch import compile_cache
from repro.models.cnn import MLPClassifier, param_count

compile_cache()

M, P, T, EPOCHS = 20, 5, 25, 2

ds = make_federated_classification(
    num_clients=M, alpha=0.1, num_samples=4000, num_eval=800,
    feature_dim=24, num_classes=10, noise=0.8, seed=0,
)
model = MLPClassifier(feature_dim=24, num_classes=10, hidden=(48, 32))
dim = param_count(model.init(jax.random.PRNGKey(0)))

strategy = FLrce(
    num_clients=M, clients_per_round=P, local_epochs=EPOCHS, dim=dim,
    es_threshold=P / 2,          # paper's recommended psi
    explore_decay=0.9,           # exploit sooner at this small T
    seed=0,
)
result = run_federated(
    model, ds, strategy, max_rounds=T, learning_rate=0.08, batch_size=32,
    seed=0, verbose=True,
)

print("\n=== FLrce quickstart summary ===")
for k, v in result.summary().items():
    print(f"  {k}: {v}")
if result.stopped_early:
    print(f"  early stopping saved {T - result.rounds_run} of {T} rounds")
