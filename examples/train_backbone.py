"""End-to-end driver (deliverable b): train a ~100M-parameter foundation
backbone for a few hundred steps on the synthetic LM stream, checkpoint it,
then use it as the FedPFT feature extractor.

    PYTHONPATH=src python examples/train_backbone.py          # ~100M, slow
    PYTHONPATH=src python examples/train_backbone.py --tiny   # CI-sized
"""
import argparse
import sys

from repro.launch import compile_cache
from repro.launch import train as train_driver


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    args, _ = ap.parse_known_args()
    if args.tiny:
        argv = ["--arch", "granite-3-2b", "--layers", "2", "--d-model",
                "256", "--steps", "60", "--batch", "4", "--seq", "128",
                "--ckpt", "/tmp/backbone_tiny.npz"]
    else:
        # ~100M params: 12 layers × d_model 768 (+ embeddings)
        argv = ["--arch", "granite-3-2b", "--layers", "12", "--d-model",
                "768", "--steps", "300", "--batch", "8", "--seq", "512",
                "--ckpt", "/tmp/backbone_100m.npz"]
    loss = train_driver.main(argv)
    print(f"final loss {loss:.4f} — checkpoint written.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
