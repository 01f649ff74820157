"""Global samples through complete optimizer steps in the window, over the
window.  Every step ends in a host read of its loss; the window closes at the
end of the first step that ends at or after ``--seconds``."""


def read(record, cell):
    ends = record["step_ends"]
    return len(ends) * record["samples_per_step"] / ends[-1]
