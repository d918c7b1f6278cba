"""Device time, in ms, of one dispatch of the plane's batched select
program (``serve.control.batched_select_program`` over ``ddpg`` select:
actor, K nearest candidates, critic argmax)."""
import readers


def read(run):
    return readers.mean_module_ms(
        run, readers.program_prefix(run.counters["program"]))
