"""``step_mfu.train``'s reading for the cells that report
``train_tokens_per_s``: model FLOP/s of the training steps in the window
over the chips' peak."""
import os

from bench.harness import load_module

read = load_module(os.path.join(os.path.dirname(__file__),
                                "step_mfu.train.py"), "step_mfu.train").read
