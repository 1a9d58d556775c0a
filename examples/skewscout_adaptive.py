"""SkewScout in action: the same training job under mild and heavy skew.

Watch the controller probe remote partitions (model traveling), measure
accuracy loss, and walk Gaia's significance threshold up (mild skew: save
communication) or down (heavy skew: protect accuracy) — Eq. 1 of §7.2.

  PYTHONPATH=src python examples/skewscout_adaptive.py
"""
import sys

sys.path.insert(0, "src")

from repro.compile_cache import use_compile_cache
from repro.configs.base import CommConfig
from repro.configs.cnn_zoo import CNN_ZOO
from repro.core import partition_label_skew, train_decentralized
from repro.data.synthetic import synth_images


def main():
    use_compile_cache()
    ds = synth_images(3000, seed=0, noise=0.8, class_sep=0.35)
    val = synth_images(800, seed=99, noise=0.8, class_sep=0.35)
    cfg = CNN_ZOO["gn-lenet"]

    for skew, tag in ((0.2, "mild skew (20%)"), (1.0, "full label skew")):
        idx = partition_label_skew(ds.y, 5, skew, seed=1)
        parts = [(ds.x[i], ds.y[i]) for i in idx]
        comm = CommConfig(skewscout=True, travel_every=40, sigma_al=0.05,
                          lambda_al=50.0, lambda_c=1.0, tuner="hill")
        r = train_decentralized(cfg, "gaia", parts, (val.x, val.y),
                                comm=comm, steps=400, batch=20, lr=0.02,
                                eval_every=200, theta_start_index=3)
        print(f"\n=== {tag} ===")
        print(f"final val_acc={r.val_acc:.3f}  "
              f"comm_savings={r.comm_savings:.1f}x vs BSP")
        print("travel log (step: theta -> new_theta, measured AL):")
        for h in r.skewscout_history:
            print(f"  step {h.step:4d}: T0={h.theta:<5} "
                  f"AL={h.accuracy_loss:.3f} C/CM={h.comm_ratio:.4f} "
                  f"J={h.objective:.3f} -> T0={h.new_theta}")


if __name__ == "__main__":
    main()
