"""Set-up: host seconds to prefill the queue through the program's bulk
insert, to the last batch's `block_until_ready` (`harness.prefill_carry`)."""


def read(view):
    return view.setup.get("prefill")
