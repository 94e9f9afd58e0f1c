"""Tokens trained per second: tokens per step x steps completed in the
window / the window's length (it opens before the first dispatch and
closes as the last step completes, so all its work and time count)."""


def read(run):
    stamps = run["record"].get("step_stamps")
    if not stamps or len(stamps) < 2:
        return None
    return (run["record"]["tokens_per_step"] * (len(stamps) - 1)
            / (stamps[-1] - stamps[0]))
