"""Device-selection walkthrough (paper §4): inspect the plans each strategy
produces for one heterogeneous client, then price them with the analytic
hop model.

This is the PLAN-ONLY view.  Since ISSUE 4 the plan also *executes*:
``examples/split_training_demo.py`` runs a federated round through the
split (staged forward/backward, boundary stages, measured LAN bytes).
Since ISSUE 5 the plan is also *controlled*: the per-device loads printed
below are exactly ``RoundFeedback.device_loads``, the field the split
controller watches to re-run this very planning when the measured
imbalance drifts — ``examples/adaptive_control_demo.py`` closes that loop.

Run: PYTHONPATH=src python examples/device_selection_demo.py
"""
from repro.config import DCGANConfig
from repro.core.devices import Client, Device
from repro.core.selection import STRATEGIES, make_plan
from repro.core.simulate import plan_epoch_time
from repro.launch.cache import use_compile_cache
from repro.models.dcgan import disc_layer_costs, disc_layer_names


def main():
    c = DCGANConfig()
    costs = disc_layer_costs(c)
    total = sum(costs.values())
    layers = [(n, 4 * costs[n] / total) for n in disc_layer_names(c)]

    client = Client("demo", [
        Device("phone", time_factor=0.4, capacity=2),    # fast, small
        Device("tablet", time_factor=1.0, capacity=2),
        Device("old-pc", time_factor=2.5, capacity=4),   # slow, roomy
        Device("watch", time_factor=0.6, capacity=1),    # fast, tiny
    ])
    print("devices (efficiency = capacity/time_factor):")
    for d in client.devices:
        print(f"  {d.device_id:8s} tf={d.time_factor:.1f} cap={d.capacity} "
              f"eff={d.efficiency:.2f}")

    print(f"\nmodel: {[n for n, _ in layers]} "
          f"(costs {[round(v, 2) for _, v in layers]})")
    for strat in STRATEGIES:
        plan = make_plan(client, layers, strat, seed=1)
        t = plan_epoch_time(plan, client, compute_unit_s=0.2)
        route = " -> ".join(f"{p.device_id}[{','.join(p.layer_names)}]"
                            for p in plan.portions)
        loads = plan.device_loads()
        imb = max(loads.values()) / (sum(loads.values()) / len(loads))
        print(f"\n{strat} (epoch {t:.1f}s, {plan.num_boundaries} LAN hops):")
        print(f"  {route}")
        print(f"  RoundFeedback.device_loads = "
              f"{ {k: round(v, 2) for k, v in loads.items()} } "
              f"(max/mean imbalance {imb:.2f} — the split controller "
              f"replans past control.imbalance_threshold)")

    print("\nnext: examples/split_training_demo.py EXECUTES a plan "
          "(staged training, measured LAN bytes, boundary leakage); "
          "examples/adaptive_control_demo.py CONTROLS it (replan + "
          "per-boundary noise from measured drift).")


if __name__ == "__main__":
    use_compile_cache()
    main()
