"""Broker gauges (parity cdn-broker/src/metrics.rs:13-21)."""

from pushcdn_tpu.proto.metrics import Gauge

NUM_USERS_CONNECTED = Gauge("cdn_num_users_connected",
                            "Users currently connected to this broker")
NUM_BROKERS_CONNECTED = Gauge("cdn_num_brokers_connected",
                              "Peer brokers currently connected to this broker")

# device-plane observability (no reference analog — the data plane the
# reference doesn't have): steps run and messages routed on-device,
# updated by broker.update_metrics() from the attached plane's counters
DEVICE_STEPS = Gauge("cdn_device_steps",
                     "Routing steps executed by the attached device plane")
DEVICE_USER_SLOTS = Gauge(
    "cdn_device_user_slots",
    "Capacity of the device plane's user table (it doubles when a "
    "connection finds it full)")
DEVICE_FRAMES_STAGED = Gauge(
    "cdn_device_frames_staged",
    "Frames accepted into the device plane's staging rings")
DEVICE_MESSAGES_ROUTED = Gauge(
    "cdn_device_messages_routed",
    "Messages delivered via the device plane's egress")
DEVICE_PLANE_DISABLED = Gauge(
    "cdn_device_plane_disabled",
    "1 once a device step failed mid-run and the plane fell open to the "
    "host path (staged frames were re-routed; nothing stages since)")
