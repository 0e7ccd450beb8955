from . import datasets, label_convert, mixup, samplers, transforms, zip_cache  # noqa: F401
from .device_prefetch import DevicePrefetcher  # noqa: F401
from .loader import (ArraySource, MapSource, DataLoader, ScaleUint8,  # noqa: F401
                     prefetch_to_device, uint8_to_unit)
from .quarantine import PoisonedData, QuarantineLog, quarantinable  # noqa: F401
