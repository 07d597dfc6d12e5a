"""Decentralized FedPFT (paper §4.2, Figure 5/6): five clients in a linear
topology, each holding a DISJOINT slice of the label space. GMMs passed
client-to-client accumulate the whole distribution in one pass.

    PYTHONPATH=src python examples/decentralized_chain.py
"""
import jax

from repro import data as D
from repro.core import decentralized as DC
from repro.core import fedpft as FP
from repro.core import gmm as G
from repro.core import head as H
from repro.launch import compile_cache


def main():
    key = jax.random.PRNGKey(1)
    n_classes = 10
    dcfg = D.DatasetConfig(n_classes=n_classes, n_per_class=150,
                           input_dim=32, class_sep=1.5)
    x, y = D.make_dataset(dcfg)
    xt, yt = D.make_dataset(dcfg, split=1)

    # client i holds ONLY classes {2i, 2i+1} — an extreme disjoint split
    clients = []
    for i in range(5):
        keep = (y == 2 * i) | (y == 2 * i + 1)
        clients.append((x[keep], y[keep]))

    cfg = FP.FedPFTConfig(
        gmm=G.GMMConfig(n_components=3, cov_type="diag", n_iter=15),
        head=H.HeadConfig(n_steps=300, lr=3e-3))
    msgs, infos = DC.run_chain(key, clients, n_classes, cfg)

    print("client | classes seen | head acc on FULL test set")
    for i, (m, info) in enumerate(zip(msgs, infos)):
        acc = float(H.accuracy(info["head"], xt, yt))
        seen = int((m.counts > 0).sum())
        print(f"   {i+1}   |      {seen:2d}      |   {acc:.4f}")
    print("→ knowledge accumulates along the chain; the last client covers "
          "all classes after ONE pass.")

    # ---- same session, Ring topology: a second lap closes the loop so the
    # EARLY clients also refit on the accumulated global knowledge ----
    from repro.fl import api as FA
    sess = FP.session_for(n_classes, cfg, topology=FA.Ring(laps=2))
    # deliberate same-stream replay: with the chain's key, the ring's first
    # lap reproduces the chain pass exactly, so the printed comparison
    # isolates what the SECOND lap adds
    res = sess.run(key, clients)  # lint: disable=KEY-REUSE
    acc0 = float(H.accuracy(res.info["per_client"][len(clients)]["head"],
                            xt, yt))
    print(f"ring (2 laps): client 1's second-lap head acc = {acc0:.4f} "
          f"(vs {float(H.accuracy(infos[0]['head'], xt, yt)):.4f} after "
          f"one chain pass); total comm = {res.info['comm_bytes']/1e3:.1f} KB")


if __name__ == "__main__":
    compile_cache.enable()
    main()
