"""Peak HBM on the fullest chip: ``peak_bytes_in_use`` plus
``peak_bytes_reserved`` of ``memory_stats()`` (buffers held plus the
region of a running program's temporaries).  A run is a fresh process,
so the lifetime peak is the run's."""

METRIC = {"name": "device.peak_hbm_gb.train", "unit": "GB",
          "better": "lower", "source": "program_counter", "layer": "device",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    peak = facts.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
