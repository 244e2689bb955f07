"""Work per round, counted from the cell's shapes: the loss-forward FLOPs
the algorithm requires and the HBM bytes each Pallas kernel must move.

Geometry: the flat-kernel plan pads the d parameters to a multiple of one
kernel block, 512 rows of 128 lanes (``n_pad``). Threefry direction
generation inside the kernels is counted as neither bytes nor FLOPs, so a
kernel bound by it reads low against its HBM roofline.
"""
from __future__ import annotations

BLOCK = 512 * 128
F32 = 4


def n_pad(fz: dict) -> int:
    return -(-fz["d"] // BLOCK) * BLOCK


def forward_flops_per_round(fz: dict, flops_per_sample: int) -> float:
    """(b2 + 1) loss forwards of b1 samples per iterate, H iterates, M
    clients; plus, averaged over rounds, the in-scan eval's two forwards
    (accuracy and loss) over ``eval_rows`` test rows every ``eval_every``
    rounds."""
    local = ((fz["b2"] + 1) * fz["local_iters"] * fz["n_participating"]
             * fz["b1"])
    evals = 2 * fz["eval_rows"] / fz["eval_every"]
    return (local + evals) * flops_per_sample


def kernel_bytes_per_round(fz: dict) -> dict:
    """HBM bytes per round, by kernel name as the compiled program calls it.

    zo_walk reads and writes a client's buffer once per direction (b2 per
    iterate, H iterates, M clients); with AirComp it also adds the noise to
    the aggregated mean once per round. zo_replay reads and writes each
    client's buffer once per iterate."""
    n = n_pad(fz)
    m, h, b2 = fz["n_participating"], fz["local_iters"], fz["b2"]
    return {"zo_walk": 2 * n * F32 * (h * b2 * m + int(fz.get("aircomp",
                                                               False))),
            "zo_replay": 2 * n * F32 * h * m}
