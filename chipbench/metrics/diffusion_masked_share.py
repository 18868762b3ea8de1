"""Noised positions the data path masked over all noised positions it made,
all batches of the run, in percent: ``hetu_diffusion_positions_total{state=
"masked"}`` over itself plus ``{state="kept"}`` (``hetu_tpu/dataloader.py
block_diffusion_noise``).  The head's rows are all noised positions whatever
the draw, but only the masked ones carry a label: it says how the draw fell,
so that a change in the draw is not read as a change in speed.  A program
without the counter gives nothing."""


def read(ctx):
    metric = (ctx.get("registry") or {}).get("hetu_diffusion_positions_total")
    if not metric or not metric["samples"]:
        return None
    by_state = {s["labels"].get("state"): s["value"]
                for s in metric["samples"]}
    total = by_state.get("masked", 0.0) + by_state.get("kept", 0.0)
    return 100.0 * by_state.get("masked", 0.0) / total if total else None
