"""xchip_lockstep.x4: % of the chips' loop rounds in the window spent
only because a lane on another chip was not done: 100 x (1 -
sum(loop_chip_rounds) / sum(loop_lane_rounds)) over the loops, from the
service's `ServiceStats`. A program without `loop_chip_rounds` reads
nothing."""


def read(run):
    chip = run.counters.get("loop_chip_rounds")
    lanes = run.counters.get("loop_lane_rounds")
    if not chip or not lanes or sum(lanes.values()) <= 0:
        return None
    return 100.0 * (1.0 - sum(chip.values()) / sum(lanes.values()))
