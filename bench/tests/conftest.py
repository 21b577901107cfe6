import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

#: a configuration small enough for the CPU: every width as published,
#: fewer samples per device and fewer selection steps.
TINY = {"d_hat": 8, "per_device": 20, "train_images": 300, "gp_steps": 20}
