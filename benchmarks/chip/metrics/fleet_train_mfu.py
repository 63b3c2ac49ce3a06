"""``train_mfu``'s model FLOP utilisation of local training, in percent,
read on the streaming fleet: the same formula over the window's
``cohort_epoch`` spans and the cell's own configuration."""
from pathlib import Path
import runpy

read = runpy.run_path(str(Path(__file__).with_name("train_mfu.py")))["read"]
