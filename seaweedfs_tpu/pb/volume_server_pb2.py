# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: volume_server.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x13volume_server.proto\x12\x0cswtpu.volume\"A\n\x12\x42\x61tchDeleteRequest\x12\x10\n\x08\x66ile_ids\x18\x01 \x03(\t\x12\x19\n\x11skip_cookie_check\x18\x02 \x01(\x08\"B\n\x13\x42\x61tchDeleteResponse\x12+\n\x07results\x18\x01 \x03(\x0b\x32\x1a.swtpu.volume.DeleteResult\"]\n\x0c\x44\x65leteResult\x12\x0f\n\x07\x66ile_id\x18\x01 \x01(\t\x12\x0e\n\x06status\x18\x02 \x01(\x05\x12\r\n\x05\x65rror\x18\x03 \x01(\t\x12\x0c\n\x04size\x18\x04 \x01(\r\x12\x0f\n\x07version\x18\x05 \x01(\r\"\xa8\x01\n\x15\x41llocateVolumeRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0bpreallocate\x18\x03 \x01(\x03\x12\x13\n\x0breplication\x18\x04 \x01(\t\x12\x0b\n\x03ttl\x18\x05 \x01(\t\x12\x1e\n\x16memory_map_max_size_mb\x18\x06 \x01(\r\x12\x11\n\tdisk_type\x18\x07 \x01(\t\"\x18\n\x16\x41llocateVolumeResponse\";\n\x12VolumeMountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\"\x15\n\x13VolumeMountResponse\")\n\x14VolumeUnmountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x17\n\x15VolumeUnmountResponse\"<\n\x13VolumeDeleteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\nonly_empty\x18\x02 \x01(\x08\"\x1a\n\x18VolumeServerLeaveRequest\"\x1b\n\x19VolumeServerLeaveResponse\"\x16\n\x14VolumeDeleteResponse\".\n\x19VolumeMarkReadonlyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x1c\n\x1aVolumeMarkReadonlyResponse\".\n\x19VolumeMarkWritableRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x1c\n\x1aVolumeMarkWritableResponse\"@\n\x16VolumeConfigureRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x13\n\x0breplication\x18\x02 \x01(\t\"(\n\x17VolumeConfigureResponse\x12\r\n\x05\x65rror\x18\x01 \x01(\t\"(\n\x13VolumeStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"q\n\x14VolumeStatusResponse\x12\x14\n\x0cis_read_only\x18\x01 \x01(\x08\x12\x13\n\x0bvolume_size\x18\x02 \x01(\x04\x12\x12\n\nfile_count\x18\x03 \x01(\x04\x12\x1a\n\x12\x66ile_deleted_count\x18\x04 \x01(\x04\"-\n\x18VacuumVolumeCheckRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"2\n\x19VacuumVolumeCheckResponse\x12\x15\n\rgarbage_ratio\x18\x01 \x01(\x01\"D\n\x1aVacuumVolumeCompactRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x13\n\x0bpreallocate\x18\x02 \x01(\x03\"K\n\x1bVacuumVolumeCompactResponse\x12\x17\n\x0fprocessed_bytes\x18\x01 \x01(\x03\x12\x13\n\x0bload_avg_1m\x18\x02 \x01(\x02\".\n\x19VacuumVolumeCommitRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"G\n\x1aVacuumVolumeCommitResponse\x12\x14\n\x0cis_read_only\x18\x01 \x01(\x08\x12\x13\n\x0bvolume_size\x18\x02 \x01(\x04\"/\n\x1aVacuumVolumeCleanupRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x1d\n\x1bVacuumVolumeCleanupResponse\"-\n\x17\x44\x65leteCollectionRequest\x12\x12\n\ncollection\x18\x01 \x01(\t\"\x1a\n\x18\x44\x65leteCollectionResponse\",\n\x17VolumeSyncStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\xc4\x01\n\x18VolumeSyncStatusResponse\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0breplication\x18\x04 \x01(\t\x12\x0b\n\x03ttl\x18\x05 \x01(\t\x12\x13\n\x0btail_offset\x18\x06 \x01(\x04\x12\x18\n\x10\x63ompact_revision\x18\x07 \x01(\r\x12\x15\n\ridx_file_size\x18\x08 \x01(\x04\x12\x19\n\x11last_append_at_ns\x18\t \x01(\x04\"C\n\x1cVolumeIncrementalCopyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08since_ns\x18\x02 \x01(\x04\"5\n\x1dVolumeIncrementalCopyResponse\x12\x14\n\x0c\x66ile_content\x18\x01 \x01(\x0c\"\x89\x01\n\x11VolumeCopyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0breplication\x18\x03 \x01(\t\x12\x0b\n\x03ttl\x18\x04 \x01(\t\x12\x18\n\x10source_data_node\x18\x05 \x01(\t\x12\x11\n\tdisk_type\x18\x06 \x01(\t\"/\n\x12VolumeCopyResponse\x12\x19\n\x11last_append_at_ns\x18\x01 \x01(\x04\"\\\n\x17VolumeTailSenderRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08since_ns\x18\x02 \x01(\x04\x12\x1c\n\x14idle_timeout_seconds\x18\x03 \x01(\r\"G\n\x18VolumeTailSenderResponse\x12\x15\n\rneedle_record\x18\x01 \x01(\x0c\x12\x14\n\x0c\x61ppend_at_ns\x18\x02 \x01(\x04\"|\n\x19VolumeTailReceiverRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08since_ns\x18\x02 \x01(\x04\x12\x1c\n\x14idle_timeout_seconds\x18\x03 \x01(\r\x12\x1c\n\x14source_volume_server\x18\x04 \x01(\t\".\n\x1aVolumeTailReceiverResponse\x12\x10\n\x08received\x18\x01 \x01(\x04\"\xb3\x01\n\x0f\x43opyFileRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x0b\n\x03\x65xt\x18\x02 \x01(\t\x12\x1b\n\x13\x63ompaction_revision\x18\x03 \x01(\r\x12\x13\n\x0bstop_offset\x18\x04 \x01(\x04\x12\x12\n\ncollection\x18\x05 \x01(\t\x12\x14\n\x0cis_ec_volume\x18\x06 \x01(\x08\x12$\n\x1cignore_source_file_not_found\x18\x07 \x01(\x08\"@\n\x10\x43opyFileResponse\x12\x14\n\x0c\x66ile_content\x18\x01 \x01(\x0c\x12\x16\n\x0emodified_ts_ns\x18\x02 \x01(\x03\"\x81\x01\n\x1dVolumeEcShardsGenerateRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0b\x64\x61ta_shards\x18\x03 \x01(\r\x12\x15\n\rparity_shards\x18\x04 \x01(\r\x12\r\n\x05\x63odec\x18\x05 \x01(\t\" \n\x1eVolumeEcShardsGenerateResponse\"\xb5\x01\n\"VolumeEcShardsGenerateBatchRequest\x12\x12\n\nvolume_ids\x18\x01 \x03(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0b\x64\x61ta_shards\x18\x03 \x01(\r\x12\x15\n\rparity_shards\x18\x04 \x01(\r\x12\r\n\x05\x63odec\x18\x05 \x01(\t\x12,\n\x07targets\x18\x06 \x03(\x0b\x32\x1b.swtpu.volume.EcShardTarget\"\xa9\x01\n#VolumeEcShardsGenerateBatchResponse\x12\x1a\n\x12\x65ncoded_volume_ids\x18\x01 \x03(\r\x12\x13\n\x0b\x64\x61ta_shards\x18\x02 \x01(\r\x12\x15\n\rparity_shards\x18\x03 \x01(\r\x12\r\n\x05\x63odec\x18\x04 \x01(\t\x12+\n\x06pushed\x18\x05 \x03(\x0b\x32\x1b.swtpu.volume.EcShardTarget\"F\n\rEcShardTarget\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x0f\n\x07\x61\x64\x64ress\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\"\xb6\x01\n\x1cVolumeEcShardsReceiveRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\x12\x12\n\nshard_size\x18\x04 \x01(\x04\x12\x10\n\x08shard_id\x18\x05 \x01(\r\x12\x0e\n\x06offset\x18\x06 \x01(\x04\x12\x0c\n\x04\x64\x61ta\x18\x07 \x01(\x0c\x12\x0b\n\x03\x65xt\x18\x08 \x01(\t\x12\x0b\n\x03vif\x18\t \x01(\x0c\"J\n\x1dVolumeEcShardsReceiveResponse\x12\x16\n\x0e\x62ytes_received\x18\x01 \x01(\x04\x12\x11\n\tshard_ids\x18\x02 \x03(\r\"E\n\x1cVolumeEcShardsRebuildRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\"e\n\x1dVolumeEcShardsRebuildResponse\x12\x19\n\x11rebuilt_shard_ids\x18\x01 \x03(\r\x12\x12\n\nbytes_read\x18\x02 \x01(\x04\x12\x15\n\rbytes_written\x18\x03 \x01(\x04\"\xb4\x01\n\x19VolumeEcShardsCopyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\x12\x15\n\rcopy_ecx_file\x18\x04 \x01(\x08\x12\x18\n\x10source_data_node\x18\x05 \x01(\t\x12\x15\n\rcopy_ecj_file\x18\x06 \x01(\x08\x12\x15\n\rcopy_vif_file\x18\x07 \x01(\x08\"\x1c\n\x1aVolumeEcShardsCopyResponse\"B\n\x19VolumeEcShardsInfoRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\"\xb0\x01\n\x1aVolumeEcShardsInfoResponse\x12\x13\n\x0b\x64\x61ta_shards\x18\x01 \x01(\r\x12\x15\n\rparity_shards\x18\x02 \x01(\r\x12\x10\n\x08\x64\x61t_size\x18\x03 \x01(\x04\x12\x17\n\x0flocal_shard_ids\x18\x04 \x03(\r\x12\r\n\x05\x63odec\x18\x05 \x01(\t\x12\x12\n\nshard_size\x18\x06 \x01(\x04\x12\x18\n\x10remote_shard_ids\x18\x07 \x03(\r\"x\n\"VolumeEcShardsCopyByRebuildRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\x12\x18\n\x10source_data_node\x18\x04 \x01(\t\"k\n#VolumeEcShardsCopyByRebuildResponse\x12\x19\n\x11rebuilt_shard_ids\x18\x01 \x03(\r\x12\x12\n\nbytes_read\x18\x02 \x01(\x04\x12\x15\n\rbytes_written\x18\x03 \x01(\x04\"o\n\x19VolumeEcShardsMoveRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\x12\x18\n\x10source_data_node\x18\x04 \x01(\t\"\x1c\n\x1aVolumeEcShardsMoveResponse\"W\n\x1bVolumeEcShardsDeleteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\"\x1e\n\x1cVolumeEcShardsDeleteResponse\"V\n\x1aVolumeEcShardsMountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\"\x1d\n\x1bVolumeEcShardsMountResponse\"D\n\x1cVolumeEcShardsUnmountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x11\n\tshard_ids\x18\x03 \x03(\r\"\x1f\n\x1dVolumeEcShardsUnmountResponse\"\x83\x02\n\x18VolumeEcShardReadRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08shard_id\x18\x02 \x01(\r\x12\x0e\n\x06offset\x18\x03 \x01(\x03\x12\x0c\n\x04size\x18\x04 \x01(\x03\x12\x10\n\x08\x66ile_key\x18\x05 \x01(\x04\x12\x18\n\x10\x66ragment_offsets\x18\x06 \x03(\x04\x12\x18\n\x10\x66ragment_lengths\x18\x07 \x03(\x04\x12\x16\n\x0e\x63ombine_matrix\x18\x08 \x01(\x0c\x12\x14\n\x0c\x63ombine_rows\x18\t \x01(\r\x12\x18\n\x10gather_shard_ids\x18\n \x03(\r\x12\x16\n\x0egather_sources\x18\x0b \x03(\t\"=\n\x19VolumeEcShardReadResponse\x12\x0c\n\x04\x64\x61ta\x18\x01 \x01(\x0c\x12\x12\n\nis_deleted\x18\x02 \x01(\x08\"e\n\x19VolumeEcBlobDeleteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x10\n\x08\x66ile_key\x18\x03 \x01(\x04\x12\x0f\n\x07version\x18\x04 \x01(\r\"\x1c\n\x1aVolumeEcBlobDeleteResponse\"F\n\x1dVolumeEcShardsToVolumeRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\" \n\x1eVolumeEcShardsToVolumeResponse\"0\n\x1bReadVolumeFileStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\xff\x01\n\x1cReadVolumeFileStatusResponse\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\"\n\x1aidx_file_timestamp_seconds\x18\x02 \x01(\x04\x12\x15\n\ridx_file_size\x18\x03 \x01(\x04\x12\"\n\x1a\x64\x61t_file_timestamp_seconds\x18\x04 \x01(\x04\x12\x15\n\rdat_file_size\x18\x05 \x01(\x04\x12\x12\n\nfile_count\x18\x06 \x01(\x04\x12\x1b\n\x13\x63ompaction_revision\x18\x07 \x01(\r\x12\x12\n\ncollection\x18\x08 \x01(\t\x12\x11\n\tdisk_type\x18\t \x01(\t\"2\n\x0bPingRequest\x12\x0e\n\x06target\x18\x01 \x01(\t\x12\x13\n\x0btarget_type\x18\x02 \x01(\t\"S\n\x0cPingResponse\x12\x15\n\rstart_time_ns\x18\x01 \x01(\x03\x12\x16\n\x0eremote_time_ns\x18\x02 \x01(\x03\x12\x14\n\x0cstop_time_ns\x18\x03 \x01(\x03\"\x1b\n\x19VolumeServerStatusRequest\"\xb1\x01\n\x1aVolumeServerStatusResponse\x12/\n\rdisk_statuses\x18\x01 \x03(\x0b\x32\x18.swtpu.volume.DiskStatus\x12.\n\rmemory_status\x18\x02 \x01(\x0b\x32\x17.swtpu.volume.MemStatus\x12\x0f\n\x07version\x18\x03 \x01(\t\x12\x13\n\x0b\x64\x61ta_center\x18\x04 \x01(\t\x12\x0c\n\x04rack\x18\x05 \x01(\t\"\x81\x01\n\nDiskStatus\x12\x0b\n\x03\x64ir\x18\x01 \x01(\t\x12\x0b\n\x03\x61ll\x18\x02 \x01(\x04\x12\x0c\n\x04used\x18\x03 \x01(\x04\x12\x0c\n\x04\x66ree\x18\x04 \x01(\x04\x12\x14\n\x0cpercent_free\x18\x05 \x01(\x02\x12\x14\n\x0cpercent_used\x18\x06 \x01(\x02\x12\x11\n\tdisk_type\x18\x07 \x01(\t\"4\n\tMemStatus\x12\x0b\n\x03\x61ll\x18\x02 \x01(\x04\x12\x0c\n\x04used\x18\x03 \x01(\x04\x12\x0c\n\x04\x66ree\x18\x04 \x01(\x04\"A\n\x19VolumeNeedleStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x11\n\tneedle_id\x18\x02 \x01(\x04\"~\n\x1aVolumeNeedleStatusResponse\x12\x11\n\tneedle_id\x18\x01 \x01(\x04\x12\x0e\n\x06\x63ookie\x18\x02 \x01(\r\x12\x0c\n\x04size\x18\x03 \x01(\r\x12\x15\n\rlast_modified\x18\x04 \x01(\x04\x12\x0b\n\x03\x63rc\x18\x05 \x01(\r\x12\x0b\n\x03ttl\x18\x06 \x01(\t\"\xd3\x03\n\x0cQueryRequest\x12\x15\n\rfrom_file_ids\x18\x01 \x03(\t\x12\x31\n\x06\x66ilter\x18\x02 \x01(\x0b\x32!.swtpu.volume.QueryRequest.Filter\x12\x13\n\x0bprojections\x18\x03 \x03(\t\x12J\n\x13input_serialization\x18\x04 \x01(\x0b\x32-.swtpu.volume.QueryRequest.InputSerialization\x12L\n\x14output_serialization\x18\x05 \x01(\x0b\x32..swtpu.volume.QueryRequest.OutputSerialization\x1a\x37\n\x06\x46ilter\x12\r\n\x05\x66ield\x18\x01 \x01(\t\x12\x0f\n\x07operand\x18\x02 \x01(\t\x12\r\n\x05value\x18\x03 \x01(\t\x1aS\n\x12InputSerialization\x12\x0e\n\x06\x66ormat\x18\x01 \x01(\t\x12\x15\n\rcsv_delimiter\x18\x02 \x01(\t\x12\x16\n\x0e\x63sv_has_header\x18\x03 \x01(\x08\x1a<\n\x13OutputSerialization\x12\x0e\n\x06\x66ormat\x18\x01 \x01(\t\x12\x15\n\rcsv_delimiter\x18\x02 \x01(\t\" \n\rQueriedStripe\x12\x0f\n\x07records\x18\x01 \x01(\x0c\"\x88\x01\n VolumeTierMoveDatToRemoteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12 \n\x18\x64\x65stination_backend_name\x18\x03 \x01(\t\x12\x1b\n\x13keep_local_dat_file\x18\x04 \x01(\x08\"S\n!VolumeTierMoveDatToRemoteResponse\x12\x11\n\tprocessed\x18\x01 \x01(\x03\x12\x1b\n\x13processedPercentage\x18\x02 \x01(\x02\"i\n\"VolumeTierMoveDatFromRemoteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x1c\n\x14keep_remote_dat_file\x18\x03 \x01(\x08\"U\n#VolumeTierMoveDatFromRemoteResponse\x12\x11\n\tprocessed\x18\x01 \x01(\x03\x12\x1b\n\x13processedPercentage\x18\x02 \x01(\x02\"N\n\x12VolumeScrubRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x0e\n\x06\x64\x65vice\x18\x02 \x01(\t\x12\x15\n\rtime_budget_s\x18\x03 \x01(\x01\"\xee\x01\n\x13VolumeScrubResponse\x12?\n\x07results\x18\x01 \x03(\x0b\x32..swtpu.volume.VolumeScrubResponse.VolumeResult\x1a\x95\x01\n\x0cVolumeResult\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x0f\n\x07scanned\x18\x02 \x01(\x04\x12\x1a\n\x12\x63orrupt_needle_ids\x18\x03 \x03(\x04\x12\x15\n\rbytes_checked\x18\x04 \x01(\x04\x12\x11\n\telapsed_s\x18\x05 \x01(\x01\x12\x0c\n\x04mode\x18\x06 \x01(\t\x12\r\n\x05\x65rror\x18\x07 \x01(\tb\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'volume_server_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _BATCHDELETEREQUEST._serialized_start=37
  _BATCHDELETEREQUEST._serialized_end=102
  _BATCHDELETERESPONSE._serialized_start=104
  _BATCHDELETERESPONSE._serialized_end=170
  _DELETERESULT._serialized_start=172
  _DELETERESULT._serialized_end=265
  _ALLOCATEVOLUMEREQUEST._serialized_start=268
  _ALLOCATEVOLUMEREQUEST._serialized_end=436
  _ALLOCATEVOLUMERESPONSE._serialized_start=438
  _ALLOCATEVOLUMERESPONSE._serialized_end=462
  _VOLUMEMOUNTREQUEST._serialized_start=464
  _VOLUMEMOUNTREQUEST._serialized_end=523
  _VOLUMEMOUNTRESPONSE._serialized_start=525
  _VOLUMEMOUNTRESPONSE._serialized_end=546
  _VOLUMEUNMOUNTREQUEST._serialized_start=548
  _VOLUMEUNMOUNTREQUEST._serialized_end=589
  _VOLUMEUNMOUNTRESPONSE._serialized_start=591
  _VOLUMEUNMOUNTRESPONSE._serialized_end=614
  _VOLUMEDELETEREQUEST._serialized_start=616
  _VOLUMEDELETEREQUEST._serialized_end=676
  _VOLUMESERVERLEAVEREQUEST._serialized_start=678
  _VOLUMESERVERLEAVEREQUEST._serialized_end=704
  _VOLUMESERVERLEAVERESPONSE._serialized_start=706
  _VOLUMESERVERLEAVERESPONSE._serialized_end=733
  _VOLUMEDELETERESPONSE._serialized_start=735
  _VOLUMEDELETERESPONSE._serialized_end=757
  _VOLUMEMARKREADONLYREQUEST._serialized_start=759
  _VOLUMEMARKREADONLYREQUEST._serialized_end=805
  _VOLUMEMARKREADONLYRESPONSE._serialized_start=807
  _VOLUMEMARKREADONLYRESPONSE._serialized_end=835
  _VOLUMEMARKWRITABLEREQUEST._serialized_start=837
  _VOLUMEMARKWRITABLEREQUEST._serialized_end=883
  _VOLUMEMARKWRITABLERESPONSE._serialized_start=885
  _VOLUMEMARKWRITABLERESPONSE._serialized_end=913
  _VOLUMECONFIGUREREQUEST._serialized_start=915
  _VOLUMECONFIGUREREQUEST._serialized_end=979
  _VOLUMECONFIGURERESPONSE._serialized_start=981
  _VOLUMECONFIGURERESPONSE._serialized_end=1021
  _VOLUMESTATUSREQUEST._serialized_start=1023
  _VOLUMESTATUSREQUEST._serialized_end=1063
  _VOLUMESTATUSRESPONSE._serialized_start=1065
  _VOLUMESTATUSRESPONSE._serialized_end=1178
  _VACUUMVOLUMECHECKREQUEST._serialized_start=1180
  _VACUUMVOLUMECHECKREQUEST._serialized_end=1225
  _VACUUMVOLUMECHECKRESPONSE._serialized_start=1227
  _VACUUMVOLUMECHECKRESPONSE._serialized_end=1277
  _VACUUMVOLUMECOMPACTREQUEST._serialized_start=1279
  _VACUUMVOLUMECOMPACTREQUEST._serialized_end=1347
  _VACUUMVOLUMECOMPACTRESPONSE._serialized_start=1349
  _VACUUMVOLUMECOMPACTRESPONSE._serialized_end=1424
  _VACUUMVOLUMECOMMITREQUEST._serialized_start=1426
  _VACUUMVOLUMECOMMITREQUEST._serialized_end=1472
  _VACUUMVOLUMECOMMITRESPONSE._serialized_start=1474
  _VACUUMVOLUMECOMMITRESPONSE._serialized_end=1545
  _VACUUMVOLUMECLEANUPREQUEST._serialized_start=1547
  _VACUUMVOLUMECLEANUPREQUEST._serialized_end=1594
  _VACUUMVOLUMECLEANUPRESPONSE._serialized_start=1596
  _VACUUMVOLUMECLEANUPRESPONSE._serialized_end=1625
  _DELETECOLLECTIONREQUEST._serialized_start=1627
  _DELETECOLLECTIONREQUEST._serialized_end=1672
  _DELETECOLLECTIONRESPONSE._serialized_start=1674
  _DELETECOLLECTIONRESPONSE._serialized_end=1700
  _VOLUMESYNCSTATUSREQUEST._serialized_start=1702
  _VOLUMESYNCSTATUSREQUEST._serialized_end=1746
  _VOLUMESYNCSTATUSRESPONSE._serialized_start=1749
  _VOLUMESYNCSTATUSRESPONSE._serialized_end=1945
  _VOLUMEINCREMENTALCOPYREQUEST._serialized_start=1947
  _VOLUMEINCREMENTALCOPYREQUEST._serialized_end=2014
  _VOLUMEINCREMENTALCOPYRESPONSE._serialized_start=2016
  _VOLUMEINCREMENTALCOPYRESPONSE._serialized_end=2069
  _VOLUMECOPYREQUEST._serialized_start=2072
  _VOLUMECOPYREQUEST._serialized_end=2209
  _VOLUMECOPYRESPONSE._serialized_start=2211
  _VOLUMECOPYRESPONSE._serialized_end=2258
  _VOLUMETAILSENDERREQUEST._serialized_start=2260
  _VOLUMETAILSENDERREQUEST._serialized_end=2352
  _VOLUMETAILSENDERRESPONSE._serialized_start=2354
  _VOLUMETAILSENDERRESPONSE._serialized_end=2425
  _VOLUMETAILRECEIVERREQUEST._serialized_start=2427
  _VOLUMETAILRECEIVERREQUEST._serialized_end=2551
  _VOLUMETAILRECEIVERRESPONSE._serialized_start=2553
  _VOLUMETAILRECEIVERRESPONSE._serialized_end=2599
  _COPYFILEREQUEST._serialized_start=2602
  _COPYFILEREQUEST._serialized_end=2781
  _COPYFILERESPONSE._serialized_start=2783
  _COPYFILERESPONSE._serialized_end=2847
  _VOLUMEECSHARDSGENERATEREQUEST._serialized_start=2850
  _VOLUMEECSHARDSGENERATEREQUEST._serialized_end=2979
  _VOLUMEECSHARDSGENERATERESPONSE._serialized_start=2981
  _VOLUMEECSHARDSGENERATERESPONSE._serialized_end=3013
  _VOLUMEECSHARDSGENERATEBATCHREQUEST._serialized_start=3016
  _VOLUMEECSHARDSGENERATEBATCHREQUEST._serialized_end=3197
  _VOLUMEECSHARDSGENERATEBATCHRESPONSE._serialized_start=3200
  _VOLUMEECSHARDSGENERATEBATCHRESPONSE._serialized_end=3369
  _ECSHARDTARGET._serialized_start=3371
  _ECSHARDTARGET._serialized_end=3441
  _VOLUMEECSHARDSRECEIVEREQUEST._serialized_start=3444
  _VOLUMEECSHARDSRECEIVEREQUEST._serialized_end=3626
  _VOLUMEECSHARDSRECEIVERESPONSE._serialized_start=3628
  _VOLUMEECSHARDSRECEIVERESPONSE._serialized_end=3702
  _VOLUMEECSHARDSREBUILDREQUEST._serialized_start=3704
  _VOLUMEECSHARDSREBUILDREQUEST._serialized_end=3773
  _VOLUMEECSHARDSREBUILDRESPONSE._serialized_start=3775
  _VOLUMEECSHARDSREBUILDRESPONSE._serialized_end=3876
  _VOLUMEECSHARDSCOPYREQUEST._serialized_start=3879
  _VOLUMEECSHARDSCOPYREQUEST._serialized_end=4059
  _VOLUMEECSHARDSCOPYRESPONSE._serialized_start=4061
  _VOLUMEECSHARDSCOPYRESPONSE._serialized_end=4089
  _VOLUMEECSHARDSINFOREQUEST._serialized_start=4091
  _VOLUMEECSHARDSINFOREQUEST._serialized_end=4157
  _VOLUMEECSHARDSINFORESPONSE._serialized_start=4160
  _VOLUMEECSHARDSINFORESPONSE._serialized_end=4336
  _VOLUMEECSHARDSCOPYBYREBUILDREQUEST._serialized_start=4338
  _VOLUMEECSHARDSCOPYBYREBUILDREQUEST._serialized_end=4458
  _VOLUMEECSHARDSCOPYBYREBUILDRESPONSE._serialized_start=4460
  _VOLUMEECSHARDSCOPYBYREBUILDRESPONSE._serialized_end=4567
  _VOLUMEECSHARDSMOVEREQUEST._serialized_start=4569
  _VOLUMEECSHARDSMOVEREQUEST._serialized_end=4680
  _VOLUMEECSHARDSMOVERESPONSE._serialized_start=4682
  _VOLUMEECSHARDSMOVERESPONSE._serialized_end=4710
  _VOLUMEECSHARDSDELETEREQUEST._serialized_start=4712
  _VOLUMEECSHARDSDELETEREQUEST._serialized_end=4799
  _VOLUMEECSHARDSDELETERESPONSE._serialized_start=4801
  _VOLUMEECSHARDSDELETERESPONSE._serialized_end=4831
  _VOLUMEECSHARDSMOUNTREQUEST._serialized_start=4833
  _VOLUMEECSHARDSMOUNTREQUEST._serialized_end=4919
  _VOLUMEECSHARDSMOUNTRESPONSE._serialized_start=4921
  _VOLUMEECSHARDSMOUNTRESPONSE._serialized_end=4950
  _VOLUMEECSHARDSUNMOUNTREQUEST._serialized_start=4952
  _VOLUMEECSHARDSUNMOUNTREQUEST._serialized_end=5020
  _VOLUMEECSHARDSUNMOUNTRESPONSE._serialized_start=5022
  _VOLUMEECSHARDSUNMOUNTRESPONSE._serialized_end=5053
  _VOLUMEECSHARDREADREQUEST._serialized_start=5056
  _VOLUMEECSHARDREADREQUEST._serialized_end=5315
  _VOLUMEECSHARDREADRESPONSE._serialized_start=5317
  _VOLUMEECSHARDREADRESPONSE._serialized_end=5378
  _VOLUMEECBLOBDELETEREQUEST._serialized_start=5380
  _VOLUMEECBLOBDELETEREQUEST._serialized_end=5481
  _VOLUMEECBLOBDELETERESPONSE._serialized_start=5483
  _VOLUMEECBLOBDELETERESPONSE._serialized_end=5511
  _VOLUMEECSHARDSTOVOLUMEREQUEST._serialized_start=5513
  _VOLUMEECSHARDSTOVOLUMEREQUEST._serialized_end=5583
  _VOLUMEECSHARDSTOVOLUMERESPONSE._serialized_start=5585
  _VOLUMEECSHARDSTOVOLUMERESPONSE._serialized_end=5617
  _READVOLUMEFILESTATUSREQUEST._serialized_start=5619
  _READVOLUMEFILESTATUSREQUEST._serialized_end=5667
  _READVOLUMEFILESTATUSRESPONSE._serialized_start=5670
  _READVOLUMEFILESTATUSRESPONSE._serialized_end=5925
  _PINGREQUEST._serialized_start=5927
  _PINGREQUEST._serialized_end=5977
  _PINGRESPONSE._serialized_start=5979
  _PINGRESPONSE._serialized_end=6062
  _VOLUMESERVERSTATUSREQUEST._serialized_start=6064
  _VOLUMESERVERSTATUSREQUEST._serialized_end=6091
  _VOLUMESERVERSTATUSRESPONSE._serialized_start=6094
  _VOLUMESERVERSTATUSRESPONSE._serialized_end=6271
  _DISKSTATUS._serialized_start=6274
  _DISKSTATUS._serialized_end=6403
  _MEMSTATUS._serialized_start=6405
  _MEMSTATUS._serialized_end=6457
  _VOLUMENEEDLESTATUSREQUEST._serialized_start=6459
  _VOLUMENEEDLESTATUSREQUEST._serialized_end=6524
  _VOLUMENEEDLESTATUSRESPONSE._serialized_start=6526
  _VOLUMENEEDLESTATUSRESPONSE._serialized_end=6652
  _QUERYREQUEST._serialized_start=6655
  _QUERYREQUEST._serialized_end=7122
  _QUERYREQUEST_FILTER._serialized_start=6920
  _QUERYREQUEST_FILTER._serialized_end=6975
  _QUERYREQUEST_INPUTSERIALIZATION._serialized_start=6977
  _QUERYREQUEST_INPUTSERIALIZATION._serialized_end=7060
  _QUERYREQUEST_OUTPUTSERIALIZATION._serialized_start=7062
  _QUERYREQUEST_OUTPUTSERIALIZATION._serialized_end=7122
  _QUERIEDSTRIPE._serialized_start=7124
  _QUERIEDSTRIPE._serialized_end=7156
  _VOLUMETIERMOVEDATTOREMOTEREQUEST._serialized_start=7159
  _VOLUMETIERMOVEDATTOREMOTEREQUEST._serialized_end=7295
  _VOLUMETIERMOVEDATTOREMOTERESPONSE._serialized_start=7297
  _VOLUMETIERMOVEDATTOREMOTERESPONSE._serialized_end=7380
  _VOLUMETIERMOVEDATFROMREMOTEREQUEST._serialized_start=7382
  _VOLUMETIERMOVEDATFROMREMOTEREQUEST._serialized_end=7487
  _VOLUMETIERMOVEDATFROMREMOTERESPONSE._serialized_start=7489
  _VOLUMETIERMOVEDATFROMREMOTERESPONSE._serialized_end=7574
  _VOLUMESCRUBREQUEST._serialized_start=7576
  _VOLUMESCRUBREQUEST._serialized_end=7654
  _VOLUMESCRUBRESPONSE._serialized_start=7657
  _VOLUMESCRUBRESPONSE._serialized_end=7895
  _VOLUMESCRUBRESPONSE_VOLUMERESULT._serialized_start=7746
  _VOLUMESCRUBRESPONSE_VOLUMERESULT._serialized_end=7895
# @@protoc_insertion_point(module_scope)
