"""``host_ms_per_tick``, read in the open-loop cell, where it sets the
capacity that the offered load has to stay under."""
import harness

read = harness.load_reader("host_ms_per_tick")
