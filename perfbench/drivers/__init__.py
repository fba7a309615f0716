"""How a deployment is stood up, loaded, drained and checked. One module per
driver, found by the `driver` key of a configuration's file. Each defines

    class Driver:
        def __init__(self, cell: spec.Cell, bench: harness.Bench): ...
        def setup(self): ...        # keys, stores, nodes, peers
        def warm(self): ...         # every shape the window uses, then the unmeasured eras
        def run_window(self, seconds): ...   # sets record.window_start/_end
        def drain(self): ...        # until what was attempted is committed
        def check(self) -> list: ...  # what is not correct; empty when all holds
        def close(self): ...        # always called

and records what the client side saw in bench.record.
"""
