"""Layer "device": idle share of the traced window (1 - union of device op
intervals over the window, averaged over the chips) and peak HBM."""


def train_device_idle_share(obs):
  reduced = obs.get('trace')
  return reduced['idle_share'] if reduced else None


def train_peak_hbm_gb(obs):
  if not obs.get('memory_peak_bytes'):
    return None
  return obs['memory_peak_bytes'] / 1e9


METRICS = {
    'train_device_idle_share': train_device_idle_share,
    'train_peak_hbm_gb': train_peak_hbm_gb,
}
