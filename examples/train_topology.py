"""Geo-WAN scenario end-to-end: gossip (D-PSGD) training over a
hierarchical topology — datacenters of LAN-connected nodes joined by
scarce WAN links — with link-level cost accounting.

Compares three fabrics on the same skewed partitions:
  full     all-to-all gossip (BSP-quality, every pair is a link)
  ring     minimal bandwidth, slowest consensus
  geo-wan  LAN cliques + WAN gateway mesh (the paper's Gaia deployment)

and prints each run's accuracy next to its LAN/WAN traffic split and the
simulated wall-clock time under the geo-wan link profile (10 Gb/s LAN,
100 Mb/s + 50 ms WAN).

  PYTHONPATH=src python examples/train_topology.py [--steps 200] [--skew 1.0]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.compile_cache import use_compile_cache
from repro.configs.base import CommConfig, FabricConfig
from repro.configs.cnn_zoo import CNN_ZOO
from repro.core.partition import partition_label_skew
from repro.core.trainer import train_decentralized
from repro.data.synthetic import synth_images
from repro.topology import LINK_PROFILES, build_topology


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--skew", type=float, default=1.0)
    ap.add_argument("--nodes", type=int, default=6)
    args = ap.parse_args()

    ds = synth_images(2400, seed=0, noise=0.8, class_sep=0.35, n_classes=6)
    val = synth_images(600, seed=99, noise=0.8, class_sep=0.35, n_classes=6)
    idx = partition_label_skew(ds.y, args.nodes, args.skew, seed=1)
    parts = [(ds.x[i], ds.y[i]) for i in idx]

    print(f"K={args.nodes} nodes, skew={args.skew}, "
          f"link profile: {LINK_PROFILES['geo-wan']}")
    for name in ("full", "ring", "geo-wan"):
        topo = build_topology(name, args.nodes)
        print(f"\n== {name}: {len(topo.edges)} edges "
              f"({len(topo.wan_edge_indices())} WAN), "
              f"spectral gap {topo.spectral_gap():.3f}")
        comm = CommConfig(strategy="dpsgd",
                          fabric=FabricConfig(topology=name,
                                              profile="geo-wan"))
        r = train_decentralized(
            CNN_ZOO["gn-lenet"], "dpsgd", parts, (val.x, val.y),
            comm=comm, steps=args.steps, batch=20, lr=0.02,
            eval_every=max(args.steps // 2, 1))
        led = r.extras["ledger"]
        print(f"   val_acc={r.val_acc:.3f}")
        print(f"   traffic: LAN {led['lan_floats']/1e6:.1f}M floats, "
              f"WAN {led['wan_floats']/1e6:.1f}M floats")
        print(f"   simulated wall-clock: {led['sim_time_s']:.2f}s "
              f"({led['sim_time_s']/args.steps*1e3:.1f} ms/step)")


if __name__ == "__main__":
    main()
