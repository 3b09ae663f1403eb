"""``idle_share.train``'s reading for the cells that report
``train_tokens_per_s``: 1 - busy union / window."""
import os

from bench.harness import load_module

read = load_module(os.path.join(os.path.dirname(__file__),
                                "idle_share.train.py"), "idle_share.train").read
