"""§Roofline: three-term roofline per (arch × shape × mesh) from the
dry-run JSONs (results/dryrun/*.json).

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / link_bw

Peaks come from the per-``device_kind`` table ``DEVICE_PEAKS``
(``runtime/telemetry.py``), resolved against the record's
``device_kind`` or the running backend; a kind not in the table raises.
Any entry can be overridden from the CLI
(``--peak-flops/--hbm-bw/--link-bw``) or per call via ``device_peaks``.

HLO_FLOPs/bytes are trip-count-weighted per-device figures (see
launch/hlo_analysis.py — XLA's cost_analysis counts loop bodies once).
MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) for train; 2·N·D_tokens
for prefill/decode forward passes.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.runtime.telemetry import DEVICE_PEAKS, device_peaks

# legacy module constants: the TPU v5e row
PEAK_FLOPS = DEVICE_PEAKS["tpu v5 lite"]["peak_flops"]
HBM_BW = DEVICE_PEAKS["tpu v5 lite"]["hbm_bw"]
LINK_BW = DEVICE_PEAKS["tpu v5 lite"]["link_bw"]

SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,        # one token per sequence
    "long_500k": 1,
}


def model_flops(rec: dict) -> float:
    """Global model FLOPs for the cell (6ND train, 2ND forward)."""
    tokens = SHAPE_TOKENS[rec["shape"]]
    n = rec["active_params"]
    mult = 6.0 if rec["shape"] == "train_4k" else 2.0
    return mult * n * tokens


def load_cells(dryrun_dir: str = "results/dryrun") -> List[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def roofline_row(rec: dict,
                 peaks: Optional[Dict[str, float]] = None
                 ) -> Optional[dict]:
    if rec.get("skipped") or rec.get("error"):
        return None
    if peaks is None:
        peaks = device_peaks(rec.get("device_kind"))
    ndev = rec["n_devices"]
    t_comp = rec["hlo_flops"] / peaks["peak_flops"]
    t_mem = rec["hlo_bytes_written"] / peaks["hbm_bw"]
    t_coll = rec["wire_bytes_per_device"] / peaks["link_bw"]
    terms = dict(compute=t_comp, memory=t_mem, collective=t_coll)
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(rec)
    useful = mf / max(rec["hlo_flops"] * ndev, 1.0)
    # roofline fraction: useful-compute time / bound (the score axis)
    bound = max(terms.values())
    frac = (mf / ndev / peaks["peak_flops"]) / max(bound, 1e-12)
    return dict(
        arch=rec["arch"], shape=rec["shape"],
        mesh="2x16x16" if rec["multi_pod"] else "16x16",
        compute_s=t_comp, memory_s=t_mem, collective_s=t_coll,
        bottleneck=bottleneck,
        model_flops=mf, useful_ratio=useful,
        roofline_frac=frac,
        mem_gb_per_dev=(rec["mem"]["argument_bytes"]
                        + rec["mem"]["temp_bytes"]) / 2 ** 30,
    )


def table(dryrun_dir: str = "results/dryrun", multi_pod: bool = False,
          peaks: Optional[Dict[str, float]] = None):
    rows = []
    for rec in load_cells(dryrun_dir):
        if rec.get("multi_pod") != multi_pod:
            continue
        r = roofline_row(rec, peaks=peaks)
        if r:
            rows.append(r)
    return rows


def run(quick: bool = True):
    rows = []
    for r in table(multi_pod=False):
        rows.append(dict(fig="roofline", **r))
    return rows


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--dryrun-dir", default="results/dryrun")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--device-kind", default=None,
                   help="peak table row to price against (default: the "
                        "record's device_kind, else the running backend)")
    p.add_argument("--peak-flops", type=float, default=None)
    p.add_argument("--hbm-bw", type=float, default=None)
    p.add_argument("--link-bw", type=float, default=None)
    args = p.parse_args(argv)
    override = dict(peak_flops=args.peak_flops, hbm_bw=args.hbm_bw,
                    link_bw=args.link_bw)
    peaks = None
    if args.device_kind or any(v is not None for v in override.values()):
        peaks = device_peaks(args.device_kind, override=override)
    rows = table(args.dryrun_dir, multi_pod=args.multi_pod, peaks=peaks)
    print(json.dumps(rows, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
