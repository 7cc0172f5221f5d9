"""Share of the traced window in which no operation ran on the device, in %,
while searches run back to back."""


def read(run):
    if not run.get("searches"):
        return None
    return run["trace"].idle_share()
