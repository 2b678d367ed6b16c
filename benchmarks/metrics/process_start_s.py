"""Process start to the first completed device operation."""


def read(run):
    return run.marks["first_device_op"] - run.marks["process_start"]
