"""Share of the restored tensor bytes that were cut out of the blocks on
the device and never left it: ``CheckpointManager.stats``
``tensor_bytes_device / (tensor_bytes_device + tensor_bytes_host_bounce)``,
delta over the traced part of the window."""


def read(win):
    device = win.trace_delta("ckpt.tensor_bytes_device")
    bounced = win.trace_delta("ckpt.tensor_bytes_host_bounce")
    if device is None or bounced is None or not device + bounced:
        return None
    return 100.0 * device / (device + bounced)
