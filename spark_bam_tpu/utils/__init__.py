from spark_bam_tpu.utils.timer import Timer, heartbeat

__all__ = ["Timer", "heartbeat"]
