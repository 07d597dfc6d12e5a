"""DP-FedPFT (paper §4.3, Theorem 4.1): formal (ε, δ)-DP via the Gaussian
mechanism on per-class (μ, Σ), plus the reconstruction-attack comparison
showing why raw-feature sharing is dangerous (§6.4).

    PYTHONPATH=src python examples/private_fl.py
"""
import jax
import jax.numpy as jnp

from repro import data as D
from repro.core import dp as DP
from repro.core import fedpft as FP
from repro.core import gmm as G
from repro.core import head as H
from repro.core import reconstruction as RA
from repro.launch import compile_cache


def main():
    key = jax.random.PRNGKey(2)
    n_classes = 8
    dcfg = D.DatasetConfig(n_classes=n_classes, n_per_class=400,
                           input_dim=32, class_sep=2.0)
    x, y = D.make_dataset(dcfg)
    xt, yt = D.make_dataset(dcfg, split=1)
    xn = lambda a: a / jnp.maximum(
        jnp.linalg.norm(a, axis=-1, keepdims=True), 1.)

    cfg = FP.FedPFTConfig(
        gmm=G.GMMConfig(n_components=1, cov_type="full", n_iter=8),
        head=H.HeadConfig(n_steps=1200, lr=3e-2), normalize_features=True)

    k_sweep, k_w, k_fit, k_samp = jax.random.split(key, 4)
    print("ε        acc     (δ=1e-2, K=1 full-cov, unit-norm features)")
    # every ε deliberately shares ONE key: identical GMM fits + synthesis
    # streams mean the sweep isolates the DP noise alone
    for eps in (0.5, 1.0, 2.0, float("inf")):
        if jnp.isfinite(eps):
            # DP-FedPFT through the unified FedSession: privatize → encode
            # → decode → batched synthesis, one session call
            head, _ = DP.run_dp_fedpft(  # lint: disable=KEY-CHAIN
                k_sweep, [(x, y)], n_classes, cfg,
                DP.DPConfig(epsilon=float(eps), delta=1e-2))
        else:
            # ε=∞ reference through the SAME session (codec included), so
            # the sweep isolates the DP noise, not wire precision
            sess = FP.session_for(n_classes, cfg, normalize_features=True)
            head = sess.run(k_sweep, [(x, y)]).model  # lint: disable=KEY-REUSE
        acc = float(H.accuracy(head, xn(xt), yt))
        print(f"{eps:<8} {acc:.4f}")

    # ---- why not just send raw features? reconstruction attack ----
    W = jax.random.normal(k_w, (32, 96)) / jnp.sqrt(32.0)
    f = lambda z: jnp.tanh(0.3 * z @ W)
    atk = RA.fit_inversion(f(x), x, RA.AttackConfig())   # attacker model
    m_raw = RA.evaluate_attack(atk, f(xt), xt, RA.AttackConfig())
    gm, cnt, _ = G.fit_classwise_gmms(k_fit, f(xt), yt, n_classes,
                                      G.GMMConfig(n_components=5,
                                                  n_iter=10))
    samples = jnp.concatenate([
        G.sample(jax.random.fold_in(k_samp, c),
                 jax.tree.map(lambda a: a[c], gm), int(cnt[c]), "diag")
        for c in range(n_classes)])
    m_gmm = RA.evaluate_attack(atk, samples, xt, RA.AttackConfig())
    print(f"\nreconstruction PSNR: raw features {m_raw['psnr_oracle']:.1f} dB"
          f"  vs  FedPFT samples {m_gmm['psnr_oracle']:.1f} dB "
          f"(lower = safer)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
