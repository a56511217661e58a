"""Shared set-up of the benchmark harness's tests: the harness directory on
``sys.path`` and a smoke-size copy of a cell for runs on the CPU."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP_DIR = os.path.join(ROOT, "benchmarks", "chip")
if CHIP_DIR not in sys.path:
    sys.path.insert(0, CHIP_DIR)

# the peaks of the chip the cells run on (peaks.json's v5e row)
V5E = "TPU v5 lite"


# the check's limit at smoke widths, set as the cell's is: CPU readings
# on seeds 2**31 + 12345, 7 and 99 gave sound runs a widest gap of 0.091
# to 0.178 (tied head) and 0.071 to 0.096 (planned head, one KV head),
# the control (one digit plane coarser) 1.136 to 1.248 and 0.700 to 1.249
SMOKE_LIMIT = 0.5


# the dense decoder's smoke widths, for a configuration without "smoke"
DENSE_SMOKE = dict(n_layers=2, d_model=128, n_heads=4, head_dim=32,
                   d_ff=256, vocab_size=8192)


def smoke_cell(name: str = "minicpm-2b.decode", root: str = ROOT,
               modules: str = CHIP_DIR) -> dict:
    """The cell ``name`` at smoke widths: the model overrides of the
    configuration's ``"smoke"`` object, or without one two layers of
    d_model 128, an 8192-token vocabulary and at most four KV heads; four
    slots, a 48-position cache and a matching short mix.  The quant spec,
    the traffic generator, the loop and the check are the cell's own; the
    limit is ``SMOKE_LIMIT``.  ``root`` and ``modules`` are
    ``run.load_cell``'s."""
    import run
    cell = copy.deepcopy(run.load_cell(name, root, modules))
    cfg = cell["config"]
    cfg["model"].update(cfg.get("smoke") or dict(
        DENSE_SMOKE, n_kv_heads=min(cfg["model"]["n_kv_heads"], 4)))
    cfg["check"]["max_logit_gap"] = SMOKE_LIMIT
    cfg["reduced"] = {k: "smoke size" for k in cfg["model"]}
    cfg["serve"].update(batch=4, max_len=48)
    cell["mix"].update(clients=4, pool=8,
                       prompt_len={"dist": "log_uniform", "min": 4,
                                   "max": 12},
                       output_len={"dist": "log_uniform", "min": 4,
                                   "max": 24})
    cell["peaks"] = run.load_peaks(V5E)
    return cell
