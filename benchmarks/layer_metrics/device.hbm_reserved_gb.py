"""``device.memory_stats()["bytes_reserved"]`` of the fullest chip when the
window ends, in GB (1e9 bytes): what the loaded programs -- the train
step above all -- hold reserved for their temporaries, which
``peak_bytes_in_use`` leaves out. The process's ``peak_bytes_reserved``
(printed in the ``window`` note) is higher where set-up's float32
reference check needed more than the step."""


def read(ctx):
    reserved = max(ctx["memory"].get("window_end_reserved_bytes", [0]),
                   default=0)
    return reserved / 1e9 if reserved else None
